#!/usr/bin/env python3
"""Readings that set a stencil cell's limit, at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1 2 .. --control-seeds 7 8 9

In one process: compile the cell once as its runs do, then for each of
``--seeds`` run one sweep on each of the traffic's input sets through the
timed path and read ``max_rel_err`` against the reference (the lower
readings); for each of ``--control-seeds`` do the same with the program's
own bfloat16 path (``CompiledStencil.__call__(dtype=bfloat16)``, the step
below the configuration's float32: the control, whose readings must fail
the limit).  It also times the plain reference (the whole sweep as one
jitted ``lax.scan`` on the canonical grid), the baseline the tiled path has
to beat.  Prints one JSON line.  The benchmark's own runs never run
this.  Needs a TPU, like ``bench/run.py``.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def readings(spec, seeds, dtype_name: str, log=harness.log) -> dict:
    """``{seed: max_rel_err}`` of one sweep per input set of each seed,
    with the program run in ``dtype_name``."""
    import jax
    import jax.numpy as jnp

    kind, cfg, traffic = spec.kind, spec.config, spec.traffic
    compiled = kind.compile_cell(cfg, traffic)
    dtype = jnp.dtype(dtype_name)
    limit = float(cfg["limits"]["max_rel_err"])
    out = {}
    for seed in seeds:
        t0 = time.perf_counter()
        sets = kind.input_sets(seed, cfg, traffic)
        outs = [(j, jax.block_until_ready(compiled(x, dtype=dtype)))
                for j, x in enumerate(sets)]
        worst, _ = kind.check(compiled, outs, sets, cfg["space"][0],
                              cfg["stencil"], limit)
        out[seed] = worst
        log(f"[{dtype_name}] seed={seed} max_rel_err={worst!r} "
            f"({time.perf_counter() - t0:.1f} s)")
    return out


def baseline_s(spec, seed: int, repeats: int = 3) -> list[float]:
    """Seconds per call of the plain reference over the cell's space,
    warm (host clock around ``block_until_ready``)."""
    import jax

    kind, cfg = spec.kind, spec.config
    x = kind.input_sets(seed, cfg, spec.traffic)[0]
    jax.block_until_ready(kind.reference_volume(x, cfg["space"][0], cfg["stencil"]))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(kind.reference_volume(x, cfg["space"][0], cfg["stencil"]))
        times.append(time.perf_counter() - t0)
    return times


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = harness.cell_spec(harness.load_manifest(ROOT), args.workload)
    devices, why = harness.accelerator(int(spec.workload["chips"]))
    if devices is None:
        print(f"control: {why}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    program = readings(spec, args.seeds, spec.config["dtype"])
    control = readings(spec, args.control_seeds, "bfloat16")
    base = baseline_s(spec, args.seeds[0])
    print(json.dumps({
        "baseline_s": base,
        "workload": args.workload, "device": devices[0].device_kind,
        "limit": spec.config["limits"]["max_rel_err"],
        "lower": max(program.values()), "upper": min(control.values()),
        "program": program, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
