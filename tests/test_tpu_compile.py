"""Every Pallas kernel compiles for a described TPU v5e at qwen2-0.5b widths.

Nothing runs: each kernel is lowered with ``interpret=False`` for one chip of
a described ``v5e:2x2`` topology and compiled by the TPU compiler that ships
with ``libtpu``. That catches what interpret mode cannot (Mosaic's block
tiling rules, unsupported in-kernel reshapes, VMEM limits). The topology is
described inside a module-scoped fixture, never at import, so that under
pytest-xdist only the worker that runs this file loads ``libtpu``.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analog import rrns
from repro.core.precision import get_policy
from repro.kernels.bfp_quantize import bfp_fake_quant_pallas
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mirage_gemm import mirage_gemm_pallas
from repro.kernels.rns_matmul import (rns_matmul_pallas,
                                      rns_matmul_pallas_channel)
from repro.kernels.rrns_decode import rrns_decode_pallas

# qwen2-0.5b: d_model 896, d_ff 4864, 14 query / 2 KV heads of 64;
# a train step of batch 4 x seq 512 gives M = 2048 tokens, prefill 4096.
M, K, N = 4096, 896, 4864
G = K // 16
# residue kernels hold (moduli x groups) copies of an (M, N) block, so they
# compile at a 256-token chunk to keep the described program inside HBM
M_RES = 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    old_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe a v5e
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    cc.reset_cache()
    if old_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


def _compiled_hlo(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _flat_moduli(moduli):
    return tuple(m for m in moduli for _ in range(G))


def _rrns_tables():
    p = get_policy("mirage_rrns")
    moduli = rrns.rrns_moduli(p)
    return moduli, rrns.get_tables(moduli, n_required=len(p.moduli),
                                   psi=p.psi)


def _case(name):
    f32, i32 = jnp.float32, jnp.int32
    moduli = get_policy("mirage_rns").moduli
    nm = len(moduli) * G
    if name == "bfp_fake_quant_pallas":
        return (lambda x: bfp_fake_quant_pallas(x, interpret=False),
                [((M, K), f32)])
    if name == "mirage_gemm_pallas":
        return (lambda x, w: mirage_gemm_pallas(x, w, interpret=False),
                [((M, K), f32), ((K, N), f32)])
    if name == "rns_matmul_pallas":
        return (lambda x, w: rns_matmul_pallas(
                    x, w, _flat_moduli(moduli), interpret=False),
                [((nm, M_RES, 16), i32), ((nm, 16, N), i32)])
    if name == "rns_matmul_pallas_channel":
        return (lambda x, w, nz: rns_matmul_pallas_channel(
                    x, w, _flat_moduli(moduli), nz, adc_bits=5,
                    interpret=False),
                [((nm, M_RES, 16), i32), ((nm, 16, N), i32),
                 ((nm, M_RES, N), f32)])
    if name == "rrns_decode_pallas":
        all_moduli, tables = _rrns_tables()
        return (lambda r: rrns_decode_pallas(r, tables, interpret=False)[0],
                [((len(all_moduli), G, M_RES, N), i32)])
    if name == "flash_attention":
        return (lambda q, k, v: flash_attention(q, k, v, interpret=False),
                [((4, 512, 14, 64), f32), ((4, 512, 2, 64), f32),
                 ((4, 512, 2, 64), f32)])
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "bfp_fake_quant_pallas", "mirage_gemm_pallas", "rns_matmul_pallas",
    "rns_matmul_pallas_channel", "rrns_decode_pallas", "flash_attention"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _case(name)
    hlo = _compiled_hlo(fn, one_chip, *shapes)
    assert "tpu_custom_call" in hlo, f"{name}: no Mosaic kernel in the HLO"
