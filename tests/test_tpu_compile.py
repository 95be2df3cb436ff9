"""Compile the main path's Pallas kernels (and the serving state's
digest) for a described TPU v5e.

Interpret mode runs the kernel bodies in Python, so it never sees what the
TPU compiler refuses: blocks that break the (8, 128) tiling rule, scalar
loads and stores in VMEM, float iotas, more VMEM than a kernel may use.
These tests lower each kernel at hla-1b widths (16 heads of 128, chunk
128) against a ``v5e:2x2`` topology that is described, not attached, and
assert that the compiled program holds the Mosaic kernel.  Nothing runs.

The topology is described inside a module fixture and nowhere else: only
one process at a time may load the TPU library, and test collection must
not depend on it.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ahla_chunk import ahla_chunk_bwd_pallas, ahla_chunk_pallas
from repro.kernels.decode_step import hla2_step_pallas
from repro.kernels.hla2_chunk import hla2_chunk_bwd_pallas, hla2_chunk_pallas

# hla-1b (configs/hla_1b.py): 16 heads of 128, chunk 128.  BH = batch 8 x
# 16 heads; n = 2048 is 16 chunks.
BH, N, D, W = 128, 2048, 128, 128
NC = N // W


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [
        jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
            s,
        )
        for s in shapes
    ]
    # conftest turns x64 on for the oracles; the chip path runs without it
    with jax.enable_x64(False):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


QKV = [_sds((BH, N, D)), _sds((BH, N, D)), _sds((BH, N, D))]
GAMMA = _sds((BH,), jnp.float32)
HLA2_STATE = tuple(
    _sds((BH,) + s, jnp.float32)
    for s in ((D, D), (D, D), (D,), (D, D), (D,))
)


@pytest.mark.parametrize("save", [False, True], ids=["fwd", "fwd_saved"])
def test_hla2_chunk_forward_compiles(one_chip, save):
    fn = functools.partial(hla2_chunk_pallas, chunk=W, save_chunk_states=save)
    _compile(fn, QKV + [GAMMA], one_chip)


def test_hla2_prefill_with_initial_state_compiles(one_chip):
    def prefill(q, k, v, g, state):
        return hla2_chunk_pallas(q, k, v, g, chunk=W, initial_state=state)

    _compile(prefill, QKV + [GAMMA, HLA2_STATE], one_chip)


def test_hla2_chunk_backward_compiles(one_chip):
    saved = tuple(
        _sds((BH, NC) + s, jnp.float32)
        for s in ((D, D), (D, D), (1, D), (D, D), (1, D))
    )
    fn = functools.partial(hla2_chunk_bwd_pallas, chunk=W)
    _compile(fn, QKV + [GAMMA, _sds((BH, N, D)), saved], one_chip)


def test_hla2_decode_step_compiles(one_chip):
    # serving layout: (slots, heads) rows of one token each
    rows = (8, 16)
    state = tuple(
        _sds(rows + s, jnp.float32)
        for s in ((D, D), (D, D), (D,), (D, D), (D,))
    )
    tok = _sds(rows + (D,))

    def step(state, q, k, v, g):
        return hla2_step_pallas(state, q, k, v, g)

    _compile(step, [state, tok, tok, tok, _sds(rows, jnp.float32)], one_chip)


# Olmo-Hybrid-7B's linear-attention layers (configs/olmo_hybrid_7b.py):
# 30 heads, key width 96, value width 192, chunk 128
OH, OD, ODV = 30, 96, 192


def _hla2_state(rows, d, dv):
    return tuple(_sds(rows + s, jnp.float32)
                 for s in ((d, d), (d, dv), (d,), (d, dv), (d,)))


def test_hla2_prefill_with_state_compiles_at_key_96_value_192(one_chip):
    """Serving prefill of one 8,192-token prompt at Olmo Hybrid's linear
    widths: key width 96 is no multiple of the 128 lanes."""
    n = 8192
    qkv = [_sds((OH, n, OD)), _sds((OH, n, OD)), _sds((OH, n, ODV))]

    def prefill(q, k, v, g, state):
        return hla2_chunk_pallas(q, k, v, g, chunk=W, initial_state=state)

    _compile(prefill, qkv + [_sds((OH,), jnp.float32),
                             _hla2_state((OH,), OD, ODV)], one_chip)


def test_hla2_decode_step_compiles_at_key_96_value_192(one_chip):
    rows = (8, OH)  # 8 slots x 30 heads

    def step(state, q, k, v, g):
        return hla2_step_pallas(state, q, k, v, g)

    _compile(step, [_hla2_state(rows, OD, ODV), _sds(rows + (OD,)),
                    _sds(rows + (OD,)), _sds(rows + (ODV,)),
                    _sds(rows, jnp.float32)], one_chip)


def test_hybrid_decode_block_updates_kv_caches_in_place(one_chip,
                                                        monkeypatch):
    """The Engine's decode block for one period of Olmo Hybrid at its
    published widths (3 HLA2 layers and 1 softmax layer), 8 slots of
    2,048 positions: the pool is donated and aliased to the output, and
    the block holds no second copy of the KV caches (a slice-out and
    write-back per layer, or a relaid-out cache, would need one)."""
    import numpy as np

    from repro.configs import get_config
    from repro.distributed import steps
    from repro.models import lm, mixer
    from repro.models.param import abstract_params
    from repro.serving import Engine, SamplingConfig

    monkeypatch.setattr(mixer, "_pallas_enabled", lambda hc: hc.use_pallas)
    cfg = get_config("olmo-hybrid-7b").replace(n_layers=4)
    slots, max_len = 8, 2048
    with jax.enable_x64(False):
        eng = Engine(cfg, None, slots=slots, max_len=max_len, block=8,
                     sampling=SamplingConfig("greedy"))
        put = lambda t: jax.tree.map(  # noqa: E731
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), t)
        params = put(abstract_params(steps.model_specs(cfg)))
        states = put(jax.eval_shape(
            lambda: lm.lm_init_states(cfg, slots, max_len)))
        col = put(jax.ShapeDtypeStruct((slots, 1), jnp.int32))
        args = (params, states, col, col,
                put(jax.ShapeDtypeStruct((slots,), jnp.bool_)),
                put(jax.eval_shape(lambda: jax.random.key(0))),
                put(jax.ShapeDtypeStruct((slots,), jnp.int32)))
        compiled = eng._decode_block.lower(
            *args, n_steps=8, scfgs=(eng.sampling,)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    kv = sum(int(np.prod(a.shape)) * a.dtype.itemsize
             for a in jax.tree.leaves(states["pos3"]))
    assert kv > 250_000_000
    assert mem.alias_size_in_bytes >= kv
    assert mem.temp_size_in_bytes < kv // 4


@pytest.mark.parametrize("save", [False, True], ids=["fwd", "fwd_saved"])
def test_ahla_chunk_forward_compiles(one_chip, save):
    fn = functools.partial(ahla_chunk_pallas, chunk=W, save_chunk_states=save)
    _compile(fn, QKV + [GAMMA], one_chip)


def test_ahla_chunk_backward_compiles(one_chip):
    saved = tuple(_sds((BH, NC, D, D + 1), jnp.float32) for _ in range(2))
    fn = functools.partial(ahla_chunk_bwd_pallas, chunk=W)
    _compile(fn, QKV + [GAMMA, _sds((BH, N, D)), saved], one_chip)


def test_state_digest_compiles_as_one_read(one_chip):
    """The prefix cache's snapshot digest and finiteness flag, over one
    sequence's whole hla-1b decode state (24 layers, 75.9 MB): reductions
    that read the state and hold no state-sized temporary."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.serving.cache import tree_digest
    from repro.serving.state_pool import tree_finite

    shapes = jax.eval_shape(
        lambda: lm.lm_init_states(get_config("hla-1b"), 1, 64))
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    with jax.enable_x64(False):
        mem = jax.jit(lambda s: (tree_finite(s), tree_digest(s))).lower(
            args).compile().memory_analysis()
    assert mem.argument_size_in_bytes > 75_000_000
    assert mem.temp_size_in_bytes < mem.argument_size_in_bytes // 32
    assert mem.output_size_in_bytes < 4096


def test_sharded_state_digest_stays_sharded(topo):
    """The same digest and flag over one sequence's hla-1b decode state
    with heads over a 1x4 ``model`` axis, as a sharded pool holds it: each
    chip reduces its own shard, so nothing gathers the state and no chip
    holds a state-sized temporary."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.configs import get_config
    from repro.distributed import steps
    from repro.serving.cache import tree_digest
    from repro.serving.state_pool import tree_finite

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    with jax.enable_x64(False):
        args = steps.state_specs(get_config("hla-1b"), 1, 64, mesh)
        assert any(a.sharding.spec != jax.sharding.PartitionSpec()
                   for a in jax.tree.leaves(args))
        with mesh:
            compiled = jax.jit(
                lambda s: (tree_finite(s), tree_digest(s))).lower(
                    args).compile()
    assert "all-gather" not in compiled.as_text()
    mem = compiled.memory_analysis()
    # per chip: a quarter of the 75.9 MB state, read in place, with no
    # more scratch than the one-chip program may use (a gathered leaf
    # alone would be 25 MB)
    assert mem.argument_size_in_bytes < 75_000_000 // 2
    assert mem.temp_size_in_bytes < 75_000_000 // 32


@pytest.mark.parametrize("prompt_len", [300, 320])
def test_sharded_prefill_compiles(topo, monkeypatch, prompt_len):
    """Serving prefill of hla-1b widths with heads over a 1x4 ``model``
    axis.  300 tokens would shard the residual stream into 75-row pieces,
    which the TPU compiler refuses; 320 shards into tile-aligned 80s."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.distributed import sharding as shd
    from repro.models import lm, mixer
    from repro.models.param import abstract_params

    # the mixer picks the kernels from the backend, which is the CPU here
    monkeypatch.setattr(mixer, "_pallas_enabled", lambda hc: hc.use_pallas)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("hla-1b").replace(n_layers=1, dtype="float32")
    specs = lm.lm_specs(cfg)
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract_params(specs), shd.param_shardings(specs, mesh),
    )
    prompt = jax.ShapeDtypeStruct((1, prompt_len), jnp.int32,
                                  sharding=NamedSharding(mesh, P()))
    with mesh, jax.enable_x64(False):
        text = jax.jit(functools.partial(lm.lm_prefill, cfg=cfg)).lower(
            params, prompt).compile().as_text()
    assert "tpu_custom_call" in text
