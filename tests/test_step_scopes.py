"""The train step's layers carry their ``jax.named_scope`` into the
compiled program: ``bench/scopes.op_scopes`` of the smoke gpt2 Sophia-G
step, compiled on the CPU as the launcher compiles it, finds every scope,
the refresh's estimator pass under ``sophia.estimate``, and the layer
scan's stacking under ``model.layers`` alone."""
import re

import pytest

SCOPES = ("model.embed", "model.layers", "model.block", "model.attn",
          "model.mlp", "loss.lm", "train.grad_clip", "optimizer.ravel",
          "optimizer.update", "sophia.estimate")


@pytest.fixture(scope="module")
def step_scopes():
    """op_scopes of the smoke gpt2 Sophia-G train step as the launcher
    compiles it (fused kernels, GNB refresh under ``lax.cond``, remat)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.launch.train import compile_train_step
    from repro.train import TrainerConfig

    from bench import scopes
    cfg = get_config("gpt2-small", smoke=True)
    tc = TrainerConfig(optimizer="sophia_g", estimator="gnb", total_steps=4,
                       warmup_steps=2, hess_interval=2, hess_subbatch=2,
                       fused_kernel=True, remat="dots")
    batch = {"tokens": np.zeros((4, 32), np.int32),
             "labels": np.zeros((4, 32), np.int32)}
    sjit, init_fn, _, _ = compile_train_step(cfg, tc, None, batch)
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    text = sjit.lower(state, batch, jnp.asarray(True)).compile().as_text()
    return scopes.op_scopes(text)


def test_train_step_carries_every_scope(step_scopes):
    found = {s for path in step_scopes.values() for s in path.split("/")}
    assert set(SCOPES) <= found
    paths = set(step_scopes.values())
    assert any(p.startswith("sophia.estimate/") and p.endswith("model.attn")
               for p in paths)


def test_scan_stacking_belongs_to_the_layer_scan(step_scopes):
    # on the CPU the scan writes each layer's residuals into the stacked
    # buffers in fusions of its own; XLA:TPU fuses most of those writes
    # into the blocks' producers instead (PERF.md §5)
    stacking = {n: p for n, p in step_scopes.items()
                if re.match(r"bitcast_dynamic-update-slice_fusion", n)}
    assert stacking
    for name, path in stacking.items():
        parts = path.split("/")
        assert parts[-1] == "model.layers", (name, path)
    assert {p.split("/")[0] for p in stacking.values()} == \
        {"model.layers", "sophia.estimate"}
