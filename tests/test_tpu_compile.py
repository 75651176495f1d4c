"""Every Pallas kernel of the main path compiles for a TPU v5e.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology lets ``jit(...).lower(...).compile()`` run Mosaic on
the kernels at the widths the chip runs them (mup-gpt training, smollm-135m
serving), and refuse what the chip would refuse — block shapes off the
(8, 128) tiling, too much VMEM.  Interpret-mode tests cannot see either.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and pytest-xdist
workers import every test file.  The persistent compilation cache is off
around these compiles (an entry written without a chip cannot be read
back).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.distributed.sharding import make_rules, shardings
from repro.kernels import ops
from repro.launch.mesh import make_mesh_shape

_PALLAS = dict(impl="pallas")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """HLO text of ``fn`` compiled for the described chip."""
    structs = [
        jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes
    ]
    return jax.jit(fn).lower(*structs).compile().as_text()


def _assert_kernel(hlo: str, n: int = 1):
    assert hlo.count("tpu_custom_call") >= n, "no Pallas kernel in the HLO"


# (name, B, S, H, K, d): mup-gpt training, smollm-135m prefill widths, the
# SmolLM-360M training cell's shapes, and the widest heads in use (gemma2's
# 256); the tiles come from the shape rule, 512 x 512 at every width
ATTN = [("mup-gpt", 8, 512, 16, 16, 64), ("smollm-135m", 2, 512, 9, 3, 64),
        ("smollm-360m", 8, 2048, 15, 5, 64), ("gemma2-2b", 1, 1024, 8, 4, 256)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name,B,S,H,K,d", ATTN, ids=[a[0] for a in ATTN])
def test_flash_attention_fwd_bwd_compiles(one_chip, name, B, S, H, K, d,
                                          dtype):
    def loss(q, k, v):
        o = ops.attention(q, k, v, scale=d ** -0.5, causal=True, **_PALLAS)
        return jnp.sum(o.astype(jnp.float32))

    q = ((B, S, H, d), dtype)
    kv = ((B, S, K, d), dtype)
    _assert_kernel(_compile(loss, one_chip, q, kv, kv))
    assert ops.RESOLVED["attention_tiles"].startswith("512x512 ")
    # forward kernel + dq and dk/dv backward kernels
    _assert_kernel(
        _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, q, kv, kv), 3
    )


# smollm-135m serving: 8 slots, 16-token pages, 128 + 32 tokens per slot
_B, _K, _G, _D, _P = 8, 3, 3, 64, 16
_C = (128 + 32) // _P
_N = _B * _C


def _pool_args(kv_dtype, T):
    q = ((_B, _K * _G, _D), jnp.float32) if T == 1 else (
        (_B, T, _K * _G, _D), jnp.float32
    )
    pool = ((_N, _K, _P, _D), kv_dtype)
    args = [q, pool, pool, ((_N, _P), jnp.int32), ((_B, _C), jnp.int32),
            ((_B,) if T == 1 else (_B, T), jnp.int32)]
    if kv_dtype == jnp.int8:
        args += [((_N, _K), jnp.float32)] * 2
    return args


@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.bfloat16, jnp.int8],
                         ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("T", [1, 16], ids=["decode", "decode_multi"])
def test_paged_decode_compiles(one_chip, kv_dtype, T):
    op = ops.decode_attention if T == 1 else ops.decode_attention_multi

    def fn(q, kp, vp, pos, tab, q_pos, *scales):
        ks, vs = scales or (None, None)
        return op(q, kp, vp, pos, tab, q_pos, scale=0.125, k_scale=ks,
                  v_scale=vs, **_PALLAS)

    _assert_kernel(_compile(fn, one_chip, *_pool_args(kv_dtype, T)))


@pytest.mark.parametrize("rows,D", [(8 * 512, 1024), (8 * 160, 576)],
                         ids=["mup-gpt", "smollm-135m"])
def test_rmsnorm_fwd_bwd_compiles(one_chip, rows, D):
    def loss(x, g):
        return jnp.sum(ops.fused_rmsnorm(x, g, **_PALLAS))

    args = (((rows, D), jnp.float32), ((D,), jnp.float32))
    _assert_kernel(_compile(loss, one_chip, *args))
    _assert_kernel(_compile(jax.grad(loss, argnums=(0, 1)), one_chip, *args))


@pytest.mark.parametrize("V", [2048, 49152], ids=["mup-gpt", "smollm-135m"])
def test_chunked_ce_fwd_bwd_compiles(one_chip, V):
    def loss(logits, labels):
        return jnp.sum(ops.softmax_cross_entropy(logits, labels, **_PALLAS))

    args = (((2048, V), jnp.float32), ((2048,), jnp.int32))
    _assert_kernel(_compile(loss, one_chip, *args))
    _assert_kernel(_compile(jax.grad(loss), one_chip, *args))


class _TpCfg:
    """mup-gpt's head counts for the decode sharding rules."""

    n_heads = 16
    n_kv_heads = 16
    d_head = 64
    parallelism = "tp"


@pytest.mark.parametrize("op", ["rmsnorm", "decode"])
def test_kernels_compile_on_tensor_parallel_mesh(topo, op):
    """On a (1, 4) mesh every kernel runs under shard_map — rmsnorm too,
    though its rows do not split over the model axis: a bare pallas_call
    in a jit over four devices cannot be partitioned."""
    mesh = make_mesh_shape((1, 4), devices=list(topo.devices))
    rep = NamedSharding(mesh, P())
    if op == "rmsnorm":
        def fn(x, g):
            return ops.fused_rmsnorm(x, g, **_PALLAS)

        shapes = [((8, 1024), jnp.float32), ((1024,), jnp.float32)]
    else:
        def fn(q, kp, vp, pos, tab, q_pos):
            return ops.decode_attention(q, kp, vp, pos, tab, q_pos,
                                        scale=0.125, **_PALLAS)

        N = 8 * _C
        shapes = [((8, 16, 64), jnp.float32),
                  ((N, 16, _P, 64), jnp.float32), ((N, 16, _P, 64), jnp.float32),
                  ((N, _P), jnp.int32), ((8, _C), jnp.int32), ((8,), jnp.int32)]
    with shardings(mesh, make_rules(mesh, cfg=_TpCfg(), kind="decode")):
        _assert_kernel(_compile(fn, rep, *shapes))
