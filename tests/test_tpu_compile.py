"""Compile for a described TPU v5e chip, without the chip.

The TPU compiler is installed with JAX, and it compiles for a topology that
is described but not attached. That catches what Pallas interpret mode on
the CPU cannot: a kernel block that overflows VMEM, or a program that does
not fit the chip's HBM. This is the only test file that describes the
chip; the description is made inside a fixture, never at import time, so
every pytest-xdist worker collects the same tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from benchmarks.common import make_linear_problem
from repro.core import compat
from repro.data import make_linear_datagen
from repro.fl import runtime as rt
from repro.kernels import ops, qsgd_rows, sign_ef_rows, topk_rows

V5E_HBM_BYTES = 16 * 2**30

# (rows, D): a fleet chunk at a small width, and the widths where whole-row
# blocks ran out of VMEM: 131072, the 1.4M-parameter cross-device model, and
# the flat dim of chip_smoke.py's 124.7M-parameter dense client
ROW_SHAPES = [(256, 4096), (64, 131072), (16, 1409024), (8, 124_668_672)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's executables cannot be read back from the persistent
    # cache, so keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


_KERNELS = {
    "topk": lambda x, s: topk_rows(x, s, mode="pallas"),
    "qsgd": lambda x, s: qsgd_rows(x, x, s, mode="pallas"),
    "sign_ef": lambda x, s: sign_ef_rows(x, x, mode="pallas"),
}


@pytest.mark.parametrize("shape", ROW_SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("kernel", sorted(_KERNELS))
def test_row_kernel_compiles_for_v5e(kernel, shape, one_chip):
    x = _sds(shape, jnp.float32, one_chip)
    scalar = _sds((), jnp.float32, one_chip)
    compiled = jax.jit(_KERNELS[kernel]).lower(x, scalar).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_topk_rows_reads_its_operand_in_place(one_chip):
    """At the GPT-2 cell's block, (2, 124,402,944): one kernel, and it
    reads the operand as it lies in HBM. No relayout copy of the operand
    and no XLA pass over it (such as a row maximum) outside the kernel."""
    d = 124_402_944
    x = _sds((2, d), jnp.float32, one_chip)
    scalar = _sds((), jnp.float32, one_chip)
    text = jax.jit(_KERNELS["topk"]).lower(x, scalar).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    instrs = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
              if re.match(r"\s*(ROOT )?%\S+ = ", ln)]
    wide = {ln.split(" = ")[0]: ln for ln in instrs
            if ln.split(" = ")[1].startswith(f"f32[2,{d}]")}
    kinds = sorted(re.search(r"\} ([\w-]+)\(", ln).group(1)
                   for ln in wide.values())
    assert kinds == ["custom-call", "parameter"], wide
    param = next(n for n, ln in wide.items() if " parameter(" in ln)
    users = [ln for ln in instrs
             if re.search(re.escape(param) + r"\b", ln.split(" = ", 1)[1])]
    assert len(users) == 1 and "tpu_custom_call" in users[0], users


@pytest.mark.parametrize("kernel", sorted(_KERNELS))
def test_row_kernel_compiles_sharded_over_v5e_2x2(kernel, topo):
    """The sharded sweep runs the kernels inside ``shard_map`` over a
    1-D mesh of the four chips, where each result must declare the mesh
    axes it varies over."""
    mesh = compat.make_mesh(topo.devices, "variants")
    rows = NamedSharding(mesh, P("variants"))
    x = _sds((4 * 64, 131072), jnp.float32, rows)
    scalar = _sds((4,), jnp.float32, rows)
    fn = jax.shard_map(lambda x, s: _KERNELS[kernel](x, s[0]), mesh=mesh,
                       in_specs=(P("variants"), P("variants")),
                       out_specs=P("variants"))
    compiled = jax.jit(fn).lower(x, scalar).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fleet_engine_step_fits_v5e(one_chip, monkeypatch):
    """One round of the README fleet (N = 1e5, 256 scheduled, chunks of
    4096, on-device data, top-k with dense EF) through the Pallas row
    kernels, as the engine dispatches them on a TPU."""
    monkeypatch.setattr(ops, "resolve_mode", lambda mode: "pallas")
    jax.clear_caches()  # drop CPU traces of the row APIs made by other tests
    params, loss_fn, _, w_star = make_linear_problem()
    cfg = rt.SimConfig(n_devices=100_000, n_scheduled=256, rounds=1,
                       chunk_size=4096, datagen=make_linear_datagen(w_star),
                       compression="topk")
    wcfg = rt.wireless.WirelessConfig(n_devices=cfg.n_devices)
    engine = rt._make_sim_fns(cfg, wcfg, loss_fn, False).engine
    args = (jax.random.PRNGKey(cfg.seed), rt.wireless.channel_params(wcfg),
            rt._resolve_cparams(cfg, params), rt._resolve_aparams(cfg),
            params)
    shapes = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), args)
    compiled = jax.jit(engine).lower(*shapes, None, None).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


def test_chunked_ef_state_is_written_in_place_per_block(one_chip,
                                                          monkeypatch):
    """The chunked client pass carries per-client EF rows as (blocks,
    chunk, D): each block writes only its own slab, at an aligned leading
    index. A flat (N, D) state on the TPU's (8, 128) tiles would put rows
    of every block in each tile, so each block's write would rewrite the
    whole state."""
    monkeypatch.setattr(ops, "resolve_mode", lambda mode: "pallas")
    jax.clear_caches()
    n, chunk, d = 8, 2, (1 << 20) + 3 * 128
    params, loss_fn, _, w_star = make_linear_problem(d=d)
    cfg = rt.SimConfig(n_devices=n, n_scheduled=4, rounds=1,
                       chunk_size=chunk, datagen=make_linear_datagen(w_star),
                       compression="topk")
    wcfg = rt.wireless.WirelessConfig(n_devices=cfg.n_devices)
    engine = rt._make_sim_fns(cfg, wcfg, loss_fn, False).engine
    args = (jax.random.PRNGKey(cfg.seed), rt.wireless.channel_params(wcfg),
            rt._resolve_cparams(cfg, params), rt._resolve_aparams(cfg),
            params)
    arg_shapes = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                              args)
    text = jax.jit(engine).lower(*arg_shapes, None, None).compile().as_text()
    writes = [ln for ln in text.splitlines()
              if " dynamic-update-slice(" in ln and "fl.client_state" in ln]
    written = [tuple(int(x) for x in re.search(
        r" = f32\[([\d,]+)\]", ln).group(1).split(",")) for ln in writes]
    assert (n // chunk, chunk, d) in written, written
    for shape, ln in zip(written, writes):
        assert not (len(shape) == 2 and shape[0] > chunk), ln
        if shape == (n // chunk, chunk, d):
            aligned = re.search(r'"is_index_aligned":\[(\w+)', ln).group(1)
            assert aligned == "true", ln
