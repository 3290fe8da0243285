"""The backbone cell of the chip benchmark cut to a size the CPU test run
holds, and the faults its check has to catch, planted in the timed path."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_small  # noqa: E402
from bench import run  # noqa: E402

CELL = "dsv2lite.session.notes"
# every width cut, the counts the cell's file keeps (a dense layer, MoE
# layers routing over more experts than are held, shared experts) kept
SMALL = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 2, "num_key_value_heads": 2, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_hidden_layers": 3, "router_experts": 16, "n_routed_experts": 4,
    "num_experts_per_tok": 2, "vocab_size": 256,
}
DATASET = {"n": 128, "length": 16}
AGENTS = ({"steps": 6, "batch": 8, "predict_block": 16},
          {"steps": 12, "hidden": [16]})


def small_cell(seed: int, seconds: float = 0.2):
    bench = run.load_benchmark()
    cell = run.load_cell(bench, CELL, seed, seconds, False)
    cfg = cell.config
    cfg.update(SMALL)
    cfg["dataset"].update(DATASET)
    for agent, cut in zip(cfg["agents"], AGENTS):
        agent.update(cut)
    return bench, cell


def clear_programs():
    """``bench_small``'s, and the backbone's predict program."""
    from repro.learners import neural
    bench_small.clear_programs()
    neural._predict.clear_cache()


def _edit_gradients(monkeypatch, edit) -> None:
    """The backbone's AdamW steps on ``edit(gradients)``."""
    from repro.learners import neural
    make = neural.adamw

    def faulty(lr, _make=make):
        opt = _make(lr)
        return opt._replace(update=lambda g, state, p, i:
                            opt.update(edit(g), state, p, i))
    monkeypatch.setattr(neural, "adamw", faulty)


def _experts_zeroed(grads):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map_with_path(
        lambda path, g: (jnp.zeros_like(g) if any(
            getattr(k, "key", None) == "moe" for k in path)
            and getattr(path[-1], "key", None) in ("wi_gate", "wi_up", "wo")
            else g), grads)


def plant(monkeypatch, fault: str) -> None:
    """``bench_small``'s faults, with the backbone's fit broken alike, and
    six of the backbone's own:

    - ``mscale_dropped``: attention scores lose YaRN's mscale^2;
    - ``topk_renormalized``: the top-k gate weights are renormalized;
    - ``shared_dropped``: the shared experts add nothing;
    - ``update_sign_flipped``: every AdamW step on the negated gradient;
    - ``minibatch_halved``: each step draws half the rows;
    - ``experts_frozen``: the routed experts' weights never move.
    """
    import dataclasses

    import jax
    from repro.learners.neural import NeuralCore
    from repro.models import attention, moe

    fit = NeuralCore.fit_counted
    if fault == "update_sign_flipped":
        _edit_gradients(monkeypatch,
                        lambda g: jax.tree.map(lambda x: -x, g))
    elif fault == "experts_frozen":
        _edit_gradients(monkeypatch, _experts_zeroed)
    elif fault == "minibatch_halved":
        def halved(self, params, key, X, onehot, w, _fit=fit):
            half = dataclasses.replace(self, batch_size=self.batch_size // 2)
            return _fit(half, params, key, X, onehot, w)
        monkeypatch.setattr(NeuralCore, "fit_counted", halved)
    elif fault == "state_unchanged":
        monkeypatch.setattr(NeuralCore, "fit_counted",
                            lambda self, params, key, X, onehot, w:
                            (params, {}))
    elif fault == "half_batch":
        def half(self, params, key, X, onehot, w, _fit=fit):
            h = X.shape[0] // 2
            return _fit(self, params, key, X[:h], onehot[:h], w[:h])
        monkeypatch.setattr(NeuralCore, "fit_counted", half)
    elif fault == "mscale_dropped":
        monkeypatch.setattr(attention, "mla_temperature", lambda cfg: 1.0)
    elif fault == "topk_renormalized":
        route = moe.router_topk

        def renormalized(params, x_flat, cfg, _route=route):
            probs, idx, aux = _route(params, x_flat, cfg)
            return probs / probs.sum(-1, keepdims=True), idx, aux
        monkeypatch.setattr(moe, "router_topk", renormalized)
    elif fault == "shared_dropped":
        apply = moe.mlp_apply
        monkeypatch.setattr(moe, "mlp_apply",
                            lambda p, x, act, _apply=apply:
                            0.0 * _apply(p, x, act))
    if fault in bench_small.FAULTS:
        bench_small.plant(monkeypatch, fault)
    clear_programs()


FAULTS = bench_small.FAULTS + ("mscale_dropped", "topk_renormalized",
                               "shared_dropped", "update_sign_flipped",
                               "minibatch_halved", "experts_frozen")


def sound_result(seed: int) -> dict:
    bench, cell = small_cell(seed)
    return run.run_cell(cell, bench, bench_small.DEVICE)


def faulty_result(seed: int, fault: str, monkeypatch) -> dict:
    bench, cell = small_cell(seed)
    plant(monkeypatch, fault)
    return run.run_cell(cell, bench, bench_small.DEVICE)


def control_checks(seed: int) -> list:
    """The check with the reference in bfloat16 put in the program's
    place: ``[(name, value, limit)]``."""
    import jax
    from bench import data_backbone
    from bench.traffic import backbone_session_queue as bq
    from bench.traffic.session_queue import session_key, with_limits
    _, cell = small_cell(seed)
    cfg = cell.config
    key = jax.random.key(seed)
    blocks, classes = data_backbone.make(cfg, jax.random.fold_in(key, 0))
    got = {"key": session_key(key, 0)}
    return with_limits(
        bq.reference_numbers(got, blocks, classes, cfg, control="bfloat16"),
        cfg["limits"]["session"])
