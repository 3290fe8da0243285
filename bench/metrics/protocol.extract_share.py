"""The ``extract`` spans (``compiled.fitted_from_result`` and
``agent_major_result``: the fitted ensemble's eager parameter slices) as a
share of the ``fit`` spans."""
from bench.shares import span_share


def read(rec):
    return span_share(rec, "extract")
