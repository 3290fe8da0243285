"""The ``plan`` spans (``Protocol._fit_compiled``: transport attach,
scheduler bind, ``compiled.plan_for``) as a share of the ``fit`` spans."""
from bench.shares import span_share


def read(rec):
    return span_share(rec, "plan")
