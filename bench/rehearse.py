"""Compile a cell's engine and print its memory analysis: for a described
TPU v5e chip, without the chip, what the chip's compiler refuses and
whether the program fits, before any chip time is spent; or, with
``--chip``, on the attached chip, beside what the runtime reports.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell>
    python3 bench/rehearse.py --workload <cell> --chip      # on the chip

With ``--chip`` the cell's window call runs twice and the line also holds
the chip's ``memory_stats()`` after it, so the compiler's figures and the
runtime's are read in one process. The engine is the one that
``run_simulation_scan`` drives. The Pallas kernels are compiled as on the
chip (``mode="pallas"``). Only one process may load the TPU compiler at a
time.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = float(2 ** 30)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--chip", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.sharding import SingleDeviceSharding
    from bench import harness
    from repro.fl import runtime as rt
    from repro.kernels import ops

    wl = harness.find_workload(harness.load_manifest(), args.workload)
    if args.chip:
        harness.use_compile_cache()
        device = harness.device_gate(1)[0]
    else:
        from jax.experimental import topologies
        ops.resolve_mode = lambda mode: mode or "pallas"
        jax.config.update("jax_enable_compilation_cache", False)
        device = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    cell = harness.build_cell(wl, 0, jax.devices()[:1])
    cfg = cell.entry.cfg
    wcfg = rt.wireless.WirelessConfig(n_devices=cfg.n_devices)
    engine = rt._get_engine(cfg, wcfg, cell.loss_fn, False)
    call_args = (jax.random.PRNGKey(cfg.seed), rt.wireless.channel_params(wcfg),
                 rt._resolve_cparams(cfg, cell.params0),
                 rt._resolve_aparams(cfg), cell.params0)
    one = SingleDeviceSharding(device)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one), call_args)
    compiled = engine.lower(*shapes, None, None).compile()
    mem = compiled.memory_analysis()
    rec = {"workload": args.workload, "D": cell.d, "chip": args.chip}
    for name in dir(mem):
        if name.endswith("_in_bytes"):
            rec[name.replace("_in_bytes", "_gib")] = getattr(mem, name) / GIB
    rec["total_gib"] = (rec["argument_size_gib"] + rec["output_size_gib"]
                        - rec["alias_size_gib"] + rec["temp_size_gib"])
    rec["pallas_kernel"] = "tpu_custom_call" in compiled.as_text()
    if args.chip:
        for j in (1, 2):
            jax.block_until_ready(cell.call(j))
        stats = device.memory_stats() or {}
        rec["memory_stats_gib"] = {k: v / GIB for k, v in stats.items()
                                   if "bytes" in k}
        rec["peak_hbm_gib"] = harness._peak_bytes(stats) / GIB
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
