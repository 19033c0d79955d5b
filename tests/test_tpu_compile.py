"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot show that a kernel meets
the TPU's tiling and VMEM rules; the TPU compiler, which is installed even
where no chip is attached, can.  Each test compiles one kernel family at
``gpt2-small`` widths (d=768, 12 heads of 64, vocab 50304, seq 1024,
batch 8) with the block sizes the autotuners pick for a v5e, and asserts
the compiled program holds the Mosaic kernel (``tpu_custom_call``).
Nothing runs: these are compiles, not chip runs.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library), and the persistent compilation
cache is off while these compiles run (a TPU executable written here could
not be read back without a chip).
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.kernels import decode_attention, flash_attention, fused_ce
from repro.kernels import sophia_update
from repro.launch import roofline

CFG = get_config("gpt2-small")
B, S = 8, 1024
H, HD = CFG.n_heads, CFG.hd
D, VP = CFG.d_model, CFG.padded_vocab
SOPHIA = dict(beta1=0.96, gamma=0.05, eps=1e-12, weight_decay=0.2,
              clip_threshold=1.0)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def chip(topo, monkeypatch):
    """One described v5e chip; the autotuners rank blocks with its peaks
    (on this host the first local device is the CPU)."""
    kind = topo.devices[0].device_kind
    monkeypatch.setattr(roofline, "device_peaks",
                        lambda k=None: roofline.CHIP_PEAKS[kind])
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled, text


def _n_params():
    from repro.models import get_model
    shapes = jax.eval_shape(lambda k: get_model(CFG).init_params(CFG, k),
                            jax.random.PRNGKey(0))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


@pytest.mark.parametrize("refresh", [False, True])
def test_sophia_update_sweep(chip, refresh):
    block = sophia_update.BLOCK
    n = -(-_n_params() // block) * block        # the engine's flat shard
    vec = _spec((n,), jnp.float32, chip)
    sc = _spec((), jnp.float32, chip)
    if refresh:
        def fn(p, m, h, g, e, lr, flag, scale):
            return sophia_update.sophia_refresh_fused_block(
                p, m, h, g, e, lr, flag, scale, beta2=0.99, interpret=False,
                **SOPHIA)
        compiled, _ = _compile(fn, *[vec] * 5, sc, sc, sc)
    else:
        def fn(p, m, h, g, lr):
            return sophia_update.sophia_fused_block(p, m, h, g, lr,
                                                    interpret=False, **SOPHIA)
        compiled, _ = _compile(fn, *[vec] * 4, sc)
    nclip = compiled.out_info[-1]
    assert nclip.shape == (n // block,) and nclip.dtype == jnp.int32


def _qkv(chip):
    return [_spec((B, H, S, HD), jnp.bfloat16, chip)] * 3


def test_flash_forward(chip):
    def fn(q, k, v):
        return flash_attention.flash_attention(q, k, v, interpret=False)
    _, text = _compile(fn, *_qkv(chip))
    assert text.count("tpu_custom_call") >= 1


def test_flash_backward_dq_dkv(chip):
    def loss(q, k, v):
        o = flash_attention.flash_attention(q, k, v, interpret=False)
        return o.astype(jnp.float32).sum()
    fused_ce.reset_kernel_calls()
    _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(chip))
    calls = fused_ce.kernel_calls()
    assert calls["attn_fwd"] and calls["attn_bwd_dq"] and \
        calls["attn_bwd_dkv"]


#: (B, H, S, hd) of the two benchmark cells' attention, and a sequence that
#: is no multiple of 128 (its q-blocks cannot slice the rows along lanes)
FLASH_SHAPES = {"gpt2-small": (B, H, S, HD), "gpt2-medium": (8, 16, 1024, 64),
                "seq-2000": (2, 4, 2000, 64)}


def _f32_rows(hlo_type):
    """The rank-4 f32 arrays of an HLO type that hold one number per query
    row (lse, delta), each as (dims, layout)."""
    for dims, layout in re.findall(r"f32\[([\d,]+)\]\{([^}]*)\}", hlo_type):
        dims = tuple(map(int, dims.split(",")))
        if len(dims) == 4 and 1 in dims[2:]:
            yield dims, layout


def _hbm_bytes(dims, layout):
    """Bytes of an f32 array in its layout: the tiling pads the two most
    minor dimensions to multiples of the first tile's shape."""
    order, _, tiling = layout.partition(":")
    tile = re.match(r"T\((\d+),(\d+)\)", tiling)
    padded = list(dims)
    if tile:
        minor = [int(d) for d in order.split(",")]
        for d, t in ((minor[0], int(tile.group(2))),
                     (minor[1], int(tile.group(1)))):
            padded[d] = -(-padded[d] // t) * t
    return 4 * math.prod(padded)


@pytest.mark.parametrize("shape", FLASH_SHAPES.values(),
                         ids=FLASH_SHAPES.keys())
def test_flash_rows_lane_dense(chip, shape):
    """Flash forward + backward at each cell's widths: the lse the forward
    writes and the lse/delta the dq and dkv kernels read are laid out in
    HBM within twice their data bytes (a (B, H, S, 1) column pads its unit
    lane axis to 128)."""
    def loss(q, k, v, g):
        o = flash_attention.flash_attention(q, k, v, interpret=False)
        return (o.astype(jnp.float32) * g).sum()
    qkv = [_spec(shape, jnp.bfloat16, chip)] * 3
    _, text = _compile(jax.grad(loss, argnums=(0, 1, 2)), *qkv,
                       _spec(shape, jnp.float32, chip))
    types = dict(re.findall(r"(%[\w.\-]+) = (\S+) ", text))
    rows = []
    for call, ty, operands in re.findall(
            r"(%[\w.\-]+) = (\(.*?\)|\S+) custom-call\(([^)]*)\).*"
            r"tpu_custom_call", text):
        rows += [(call, r) for r in _f32_rows(ty)]
        for op in re.findall(r"%[\w.\-]+", operands):
            rows += [(call, r) for r in _f32_rows(types.get(op, ""))]
    # the forward's lse; lse and delta into each of dq and dkv
    assert len(rows) >= 5, rows
    for call, (dims, layout) in rows:
        assert _hbm_bytes(dims, layout) <= 2 * 4 * math.prod(dims), \
            (call, dims, layout)


def _ce_args(chip):
    return (_spec((B, S, D), jnp.bfloat16, chip),       # pre-norm hidden
            _spec((VP, D), jnp.float32, chip),          # tied embedding
            _spec((D,), jnp.float32, chip),             # ln scale
            _spec((D,), jnp.float32, chip))             # ln bias


def _ce_kw():
    return dict(vocab_size=CFG.vocab_size, norm_kind="ln", interpret=False)


def test_fused_ce_forward(chip):
    def fn(h, w, ns, nb, lab):
        return fused_ce.fused_lm_loss(h, w, lab, norm_scale=ns, norm_bias=nb,
                                      **_ce_kw())[0]
    fused_ce.reset_kernel_calls()
    _compile(fn, *_ce_args(chip), _spec((B, S), jnp.int32, chip))
    assert fused_ce.kernel_calls()["fwd"]


def test_fused_ce_forward_sample(chip):
    def fn(h, w, ns, nb, key):
        return fused_ce.fused_lm_loss_sampled(
            h, w, key, norm_scale=ns, norm_bias=nb, **_ce_kw())[0]
    fused_ce.reset_kernel_calls()
    _compile(fn, *_ce_args(chip), _spec((2,), jnp.uint32, chip))
    assert fused_ce.kernel_calls()["fwd_sample"]


def test_fused_ce_backward(chip):
    def loss(h, w, ns, nb, lab):
        return fused_ce.fused_lm_loss(h, w, lab, norm_scale=ns, norm_bias=nb,
                                      **_ce_kw())[0]
    fused_ce.reset_kernel_calls()
    _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), *_ce_args(chip),
             _spec((B, S), jnp.int32, chip))
    calls = fused_ce.kernel_calls()
    assert any(v for k, v in calls.items() if k.startswith("bwd_"))


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_fused_ce_ragged_chunks(chip, tied):
    """Chunks of 1024 columns over gpt2-small's Vp 50304: the last W block
    runs past the array (rows when tied, lanes when untied) and the kernels
    mask it; Mosaic takes the partial block for the forward, the sampling
    forward and both backward sweeps, within the VMEM the kernels request."""
    w = _spec((VP, D) if tied else (D, VP), jnp.float32, chip)
    h = _spec((2, S, D), jnp.bfloat16, chip)
    kw = dict(vocab_size=CFG.vocab_size, transpose_w=not tied, block_n=512,
              block_v=1024, interpret=False)

    def loss(h, w, lab, key):
        return (fused_ce.fused_lm_loss(h, w, lab, **kw)[0]
                + fused_ce.fused_lm_loss_sampled(h, w, key, **kw)[0])
    fused_ce.reset_kernel_calls()
    _compile(jax.grad(loss, argnums=(0, 1)), h, w,
             _spec((2, S), jnp.int32, chip), _spec((2,), jnp.uint32, chip))
    calls = fused_ce.kernel_calls()
    assert VP % 1024 and calls["ce_ragged_calls"] == 6, calls
    assert calls["bwd_split"] == 2 and calls["fwd_sample"] == 1, calls


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_decode_attention(chip, kv_dtype):
    N, C = 4, 512
    q = _spec((N, H, HD), jnp.bfloat16, chip)
    pos = _spec((N,), jnp.int32, chip)
    if kv_dtype == "int8":
        kv = _spec((N, C, H, HD), jnp.int8, chip)
        sc = _spec((N, C), jnp.float32, chip)

        def fn(q, k, v, p, ks, vs):
            return decode_attention.decode_attention_pallas(
                q, k, v, p, k_scale=ks, v_scale=vs, interpret=False)
        _compile(fn, q, kv, kv, pos, sc, sc)
    else:
        kv = _spec((N, C, H, HD), jnp.bfloat16, chip)

        def fn(q, k, v, p):
            return decode_attention.decode_attention_pallas(
                q, k, v, p, interpret=False)
        _compile(fn, q, kv, kv, pos)


def test_sharded_train_step(topo, monkeypatch):
    """The training step on a described 2x2 mesh with every Pallas kernel
    on and the int8 gradient all-reduce: XLA cannot partition a Mosaic
    kernel, so each must run per device (distributed.sharding.per_device);
    the multi-chip scheduler options must be ones the TPU compiler knows.
    The small config keeps the compile short; widths do not change the
    partitioning."""
    from repro.distributed.sharding import set_activation_mesh
    from repro.launch.mesh import overlap_compiler_options
    from repro.launch.train import build_mesh, compile_train_step
    from repro.train import TrainerConfig, make_train_fns

    kind = topo.devices[0].device_kind
    monkeypatch.setattr(roofline, "device_peaks",
                        lambda k=None: roofline.CHIP_PEAKS[kind])
    # code that asks for the backend sees this host's CPU: steer the
    # kernels to compile natively
    for mod in (fused_ce, flash_attention, decode_attention, sophia_update):
        monkeypatch.setattr(mod, "_interpret_default", lambda: False)
    cfg = get_config("gpt2-small", smoke=True)
    tc = TrainerConfig(optimizer="sophia_g", estimator="gnb", total_steps=4,
                       warmup_steps=2, hess_interval=2, hess_subbatch=4,
                       fused_kernel=True, compress_grads=True)
    mesh = build_mesh(topo.devices)
    assert overlap_compiler_options(mesh)
    batch = {"tokens": np.zeros((4, 64), np.int32),
             "labels": np.zeros((4, 64), np.int32)}
    state_shape = jax.eval_shape(make_train_fns(cfg, tc)[0],
                                 jax.random.PRNGKey(0))

    def shaped(tree, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), tree, shardings)

    flag = jax.ShapeDtypeStruct((), jnp.bool_,
                                sharding=NamedSharding(mesh, P()))
    fused_ce.reset_kernel_calls()
    try:
        step, _, ssh, bsh = compile_train_step(cfg, tc, mesh, batch,
                                               state_shape=state_shape)
        text = step.lower(shaped(state_shape, ssh),
                          shaped(jax.eval_shape(lambda b: b, batch), bsh),
                          flag).compile().as_text()
    finally:
        set_activation_mesh(None)
    assert "tpu_custom_call" in text
    calls = fused_ce.kernel_calls()
    for k in ("attn_fwd", "attn_bwd_dq", "attn_bwd_dkv", "fwd",
              "fwd_sample"):
        assert calls.get(k), (k, calls)


def test_overlap_options_known_to_compiler(topo):
    """Every scheduler option the launcher passes on a multi-chip TPU mesh
    is one the installed TPU compiler accepts (an unknown one is a compile
    error)."""
    from repro.launch.mesh import overlap_compiler_options
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    opts = overlap_compiler_options(mesh)
    assert opts
    x = jax.ShapeDtypeStruct((1024, 512), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    compiled = jax.jit(lambda x: (x @ x.T).sum(0),
                       compiler_options=opts).lower(x).compile()
    assert "all-reduce" in compiled.as_text() or \
        "reduce-scatter" in compiled.as_text()


def _flash_programs(chip):
    qkv = [_spec((1, 2, 256, 64), jnp.bfloat16, chip)] * 3

    def loss(q, k, v):
        o = flash_attention.flash_attention(q, k, v, interpret=False)
        return o.astype(jnp.float32).sum()
    yield jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*qkv)


def _fused_ce_programs(chip):
    h = _spec((2, 128, 128), jnp.bfloat16, chip)
    w = _spec((512, 128), jnp.float32, chip)
    lab = _spec((2, 128), jnp.int32, chip)
    kw = dict(vocab_size=500, block_v=256, interpret=False)
    for schedule, block_n in (("fused", 256), ("split", 128)):
        def loss(h, w, lab, schedule=schedule, block_n=block_n):
            return fused_ce.fused_lm_loss(h, w, lab, schedule=schedule,
                                          block_n=block_n, **kw)[0]
        yield jax.jit(jax.grad(loss, argnums=(0, 1))).lower(h, w, lab)

    def sampled(h, w, key):
        return fused_ce.fused_lm_loss_sampled(h, w, key, block_n=128,
                                              **kw)[0]
    yield jax.jit(sampled).lower(h, w, _spec((2,), jnp.uint32, chip))


def _sophia_update_programs(chip):
    n = sophia_update.BLOCK
    v = _spec((n,), jnp.float32, chip)
    s = _spec((), jnp.float32, chip)
    hp = dict(beta1=0.9, beta2=0.99, eps=1e-8, weight_decay=0.1)
    su = sophia_update
    calls = [
        (lambda p, m, h, g, lr: su.sophia_fused_block(
            p, m, h, g, lr, interpret=False, **SOPHIA), (v, v, v, v, s)),
        (lambda h, e, sc: su.hessian_ema_block(
            h, e, beta2=0.99, scale=sc, interpret=False), (v, v, s)),
        (lambda p, m, h, g, e, lr, f, sc: su.sophia_refresh_fused_block(
            p, m, h, g, e, lr, f, sc, beta2=0.99, interpret=False, **SOPHIA),
         (v,) * 5 + (s,) * 3),
        (lambda p, m, h, g, e, lr, f, sc, t:
         su.adahessian_refresh_fused_block(p, m, h, g, e, lr, f, sc, t,
                                           interpret=False, **hp),
         (v,) * 5 + (s,) * 4),
        (lambda p, m, h, g, lr, t: su.adamw_fused_block(
            p, m, h, g, lr, t, interpret=False, **hp), (v,) * 4 + (s,) * 2),
        (lambda p, m, h, g, lr, t: su.adahessian_fused_block(
            p, m, h, g, lr, t, interpret=False, **hp), (v,) * 4 + (s,) * 2),
        (lambda p, m, g, lr: su.lion_fused_block(
            p, m, g, lr, beta1=0.9, beta2=0.99, weight_decay=0.1,
            interpret=False), (v,) * 3 + (s,)),
        (lambda p, m, g, lr: su.signgd_fused_block(
            p, m, g, lr, beta1=0.9, weight_decay=0.1, interpret=False),
         (v,) * 3 + (s,)),
        (lambda p, m, g, lr: su.sgd_fused_block(
            p, m, g, lr, momentum=0.9, interpret=False), (v,) * 3 + (s,)),
    ]
    for fn, args in calls:
        yield jax.jit(fn).lower(*args)


@pytest.mark.parametrize("module,programs,readers", [
    (flash_attention, _flash_programs, ("flash_attention_roofline",)),
    (fused_ce, _fused_ce_programs, ("fused_ce_roofline",)),
    (sophia_update, _sophia_update_programs, ("optimizer_update_roofline",)),
], ids=["flash_attention", "fused_ce", "sophia_update"])
def test_kernel_names_are_pinned(chip, module, programs, readers):
    """Every kernel of a kernel module compiles to a Mosaic module named
    as its ``KERNEL_NAMES`` say (what the benchmark's trace reduction
    reads, ``bench/trace.kernel_names``), and each roofline reader of the
    benchmark looks for names among them."""
    from bench import common, trace
    names = set()
    for lowered in programs(chip):
        names |= set(trace.kernel_names(lowered.compile().as_text())
                     .values())
    assert names == set(module.KERNEL_NAMES)
    for reader in readers:
        assert set(common.metric_reader(reader).KERNELS) <= names, reader
