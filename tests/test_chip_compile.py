"""Compile the main path's device programs for a TPU v5e without a chip.

The TPU compiler is installed beside the CPU backend and compiles for a
described, unattached ``v5e:2x2`` topology. It refuses what interpret
mode accepts: block shapes the tiling cannot take, too much VMEM, a
program too large for HBM. Each case compiles one program for one chip:
the Pallas kernels at smollm_360m and xlstm_350m widths, the block
programs of the GEMM and TSQR jobs at the sizes ``chip_smoke.py`` runs,
the inlined GEMM program of the chip benchmark's 1024^2 blocks (its
4096^2 groups run member by member), and the fused attention of the
benchmark's SmolLM steps, forward and backward.

The topology is described only inside the fixture. Describing it loads
the TPU library, which one process at a time may hold, so it must not
happen while test modules are imported.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.apps.gemm import _add, _matmul, gemm_dag
from repro.apps.svd import _qr_r, _singular_values, _stack_qr_r
from repro.configs import get_config
from repro.core import OptimizeConfig, compile_dag
from repro.kernels import ops as kernel_ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.linear_attention import mlstm_chunk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # Otherwise the TPU compiler writes its logs under /tmp.
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without one; keep it out.
        was_enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


def _smollm_attention():
    cfg = get_config("smollm_360m")
    return cfg.n_heads, cfg.n_kv_heads, cfg.hd


def _flash(sds):
    B, S = 8, 1024
    H, K, hd = _smollm_attention()
    fn = functools.partial(flash_attention, causal=True, block_q=128,
                           block_k=128, interpret=False)
    return fn, (sds((B, S, H, hd), jnp.bfloat16),
                sds((B, S, K, hd), jnp.bfloat16),
                sds((B, S, K, hd), jnp.bfloat16))


def _decode(sds):
    B, S = 8, 2048
    H, K, hd = _smollm_attention()
    fn = functools.partial(decode_attention, block_k=512, interpret=False)
    return fn, (sds((B, H, hd), jnp.bfloat16),
                sds((B, S, K, hd), jnp.bfloat16),
                sds((B, S, K, hd), jnp.bfloat16),
                sds((B,), jnp.int32))


def _mlstm(sds):
    cfg = get_config("xlstm_350m")
    B, S, H = 8, 1024, cfg.n_heads
    hd = int(cfg.mlstm_proj_factor * cfg.d_model) // H
    fn = functools.partial(mlstm_chunk, chunk=64, interpret=False)
    seq, gate = (B, S, H, hd), (B, S, H)
    return fn, (sds(seq, jnp.float32), sds(seq, jnp.float32),
                sds(seq, jnp.float32), sds(gate, jnp.float32),
                sds(gate, jnp.float32))


def _gemm_block(fn):
    def case(sds):
        return fn, (sds((1024, 1024), jnp.float32),) * 2
    return case


def _gemm_inlined(grid, block):
    """The program the DAG compiler makes of one output block's products
    and sums; its structure depends on the grid, not on the block size."""
    def case(sds):
        dag = compile_dag(gemm_dag(8 * grid, 8), OptimizeConfig(
            fuse_chains=False, cluster_tasks=False, coalesce_fanouts=False))
        fn = dag.tasks[dag.deps["gemm-C-0-0"][0]].fn.program
        return fn, (sds((block, block), jnp.float32),) * (2 * grid)
    return case


def _tsqr(fn, *shapes):
    def case(sds):
        return fn, tuple(sds(s, jnp.float32) for s in shapes)
    return case


CASES = {
    "flash_attention-smollm_360m": _flash,
    "decode_attention-smollm_360m": _decode,
    "mlstm_chunk-xlstm_350m": _mlstm,
    "gemm_matmul-1024": _gemm_block(_matmul),
    "gemm_add-1024": _gemm_block(_add),
    "gemm_inlined-8x8-1024": _gemm_inlined(8, 1024),
    "tsqr_qr_r-16384x128": _tsqr(_qr_r, (16384, 128)),
    "tsqr_stack_qr_r-128": _tsqr(_stack_qr_r, (128, 128), (128, 128)),
    "tsqr_singular_values-128": _tsqr(_singular_values, (128, 128)),
}


@pytest.mark.parametrize("name", list(CASES))
def test_compiles_for_one_v5e_chip(one_chip, name):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = CASES[name](sds)
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    # One program must fit one chip's 16 GB of HBM.
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9
    if name.split("-")[0] in ("flash_attention", "decode_attention",
                              "mlstm_chunk"):
        # A compiled Pallas kernel, not the interpreter's loop of XLA ops.
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", [4, 8])
def test_fused_attention_compiles_for_one_v5e_chip(one_chip, monkeypatch, batch):
    """``kernels.ops.splash_attention``'s forward and backward at the
    SmolLM cells' shapes: S 2048, 15 query and 5 KV heads of 64."""
    # The wrapper asks the backend, which is the CPU here.
    monkeypatch.setattr(kernel_ops, "interpret_mode", lambda: False)
    H, K, hd = _smollm_attention()
    q, kv = (batch, 2048, H, hd), (batch, 2048, K, hd)

    def fwd_bwd(q, k, v, d_out):
        out, vjp = jax.vjp(functools.partial(
            kernel_ops.splash_attention, causal=True, window=None,
            scale=hd ** -0.5), q, k, v)
        return (out, *vjp(d_out))

    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in (q, kv, kv, q)]
    compiled = jax.jit(fwd_bwd).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9
    # The forward kernel and the backward one, which computes dq with dk and dv.
    assert compiled.as_text().count("tpu_custom_call") == 2
