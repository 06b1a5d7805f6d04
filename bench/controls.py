"""The controls on the chip: runs of a cell at its own size and load, with a
short window, each comparing the program's windows and then each control's
(``reference.CONTROLS``) in the program's place with the reference.

    python3 bench/controls.py --workload <cell> --seeds 11,12,13 --seconds 10

One process runs every seed.  Prints one JSON line per seed with the
program's readings and each control's.  A cell's control must come out
not correct on every seed.  ``replay`` is one for every cell; ``bf16`` is
one where a window's sum or count for a key passes 256, above which
bfloat16 drops whole units (a segment of the Linear Road stream holds
about 500 reports a minute).
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH / "metrics")]

import reference  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    spec = run.load_spec(args.workload)
    run.enable_compile_cache()
    passed = {c: 0 for c in reference.CONTROLS}
    for seed in (int(s) for s in args.seeds.split(",")):
        root = tempfile.mkdtemp(prefix="bench-log-")
        gen = run.Generator(spec, seed, root)
        try:
            code, result = run.run_cell(spec, seed, args.seconds, False, gen,
                                        root, controls=reference.CONTROLS)
        finally:
            gen.kill()
            shutil.rmtree(root, ignore_errors=True)
        if code != 0:
            return code
        row = {"seed": seed, "correct": result["correct"],
               "checks": {k: v["value"] for k, v in result["checks"].items()},
               "controls": {c: {"correct": v["correct"],
                                **{k: x["value"]
                                   for k, x in v["checks"].items()}}
                            for c, v in result["controls"].items()}}
        for c, v in result["controls"].items():
            passed[c] += v["correct"]
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload,
                      "seeds_where_each_control_passed": passed}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
