"""The correctness check fails what it must fail, at a size a test run
holds, on the CPU:

* the control: the plain reference in the program's place, computed with
  every matrix product in float8 (the precision below the configuration's
  bfloat16), against the cell's own limits;
* the harness driven end to end (everything but its look for a chip) with
  the timed path broken underneath: a step that returns its state
  unchanged, and a step that leaves half of the batch out and takes the
  mean over the rest.

It also holds that the reference starts where the program starts (the same
weights from the same key, bit for bit), that the benchmark's generator
makes the batches the program's source makes, and that a sound run passes.
"""
import time

import jax
import numpy as np
import pytest

from bench import check, common, generator
from bench.kinds import train_lm
from bench.tests.helpers import tiny_spec

CELLS = [w["name"] for w in common.benchmark()["workloads"]]
SEED = 2 ** 31 + 2718


def _run(spec, faults=()):
    return train_lm.run(spec, seed=SEED, seconds=0, trace=False,
                        devices=jax.devices()[:1],
                        t_start=time.perf_counter(), faults=faults)


@pytest.mark.parametrize("config", ["gpt2-medium-355m", "gpt2-small-125m"])
def test_reference_starts_from_the_programs_weights(config):
    from repro.models import ModelConfig, get_model
    spec = tiny_spec(next(w["name"] for w in common.benchmark()["workloads"]
                          if w["config"] == config))
    m = spec["config"]["model"]
    cfg = ModelConfig(name=config, **m)
    key = train_lm.jax_key(SEED)
    ours = get_model(cfg).init_params(cfg, key)
    ref = common.reference(spec["config"]["reference"]).init_params(m, key)
    assert jax.tree.structure(ours) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_generator_makes_the_programs_batches():
    from repro.data import DataConfig, make_source
    tr = tiny_spec(CELLS[0])["traffic"]
    ours = make_source(DataConfig(seq_len=tr["seq_len"],
                                  global_batch=tr["global_batch"],
                                  vocab_size=512, seed=SEED,
                                  zipf_a=tr["zipf_a"]))
    gen = generator.make(tr, vocab_size=512, seed=SEED)
    for t in (0, 1, 7):
        a, b = ours.batch_at(t), gen.batch_at(t)
        for k in ("tokens", "labels"):
            np.testing.assert_array_equal(a[k], b[k])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = _run(tiny_spec(cell))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    spec = tiny_spec(cell)
    mod = common.reference(spec["config"]["reference"])
    n = spec["workload"]["check_steps"]
    ref = train_lm.reference_readings(spec, SEED, n)
    control = train_lm.reference_readings(spec, SEED, n,
                                          quant=mod.fp8_quant)
    ok, checks = check.judge(control, ref, spec["workload"]["limits"])
    assert not ok, checks


def _state_unchanged(step):
    def broken(state, batch, flag):
        return state, step(state, batch, flag)[1]
    return broken


@pytest.mark.parametrize("cell", CELLS)
def test_state_left_unchanged_is_not_correct(cell):
    res = _run(tiny_spec(cell), faults=(_state_unchanged,))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_batch_is_not_correct(cell, monkeypatch):
    from repro.train import trainer
    accum = trainer._accum_grads

    def half(loss_fn, params, batch, n):
        return accum(loss_fn, params, jax.tree.map(
            lambda x: x[:x.shape[0] // 2], batch), n)

    monkeypatch.setattr(trainer, "_accum_grads", half)
    res = _run(tiny_spec(cell))
    assert not res["correct"], res["checks"]
