#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic, kind and metrics are found through
``BENCHMARK.json`` (see ``bench/harness.py``).  ``--trace 0`` prints the
cell's end-to-end metrics; ``--trace 1`` profiles the window and prints its
per-layer metrics.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number compared
with its limit); the checks are also the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program under test ({ROOT / 'src' / 'repro'}) is "
              "not in this checkout", file=sys.stderr)
        return 1
    # the TPU runtime's logs go under this process's temporary directory,
    # not a fixed path shared with other checkouts
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    manifest = harness.load_manifest(ROOT)
    spec = harness.cell_spec(manifest, args.workload)
    devices, why = harness.accelerator(int(spec.workload["chips"]))
    t_devices = time.perf_counter() - T_START
    if devices is None:
        print(f"bench: {why}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev = devices[0]
    harness.log(f"[device] platform={dev.platform} kind={dev.device_kind} "
                f"count={len(devices)} compile_cache={cache} "
                f"process start to devices: {t_devices} s")
    result = harness.run_cell(spec, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_start=T_START,
                              devices=devices, root=ROOT)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
