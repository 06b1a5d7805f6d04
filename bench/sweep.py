"""Rate sweep: one configuration at a few fixed open-loop offered rates, in
one process on the chip; reports how fast the backlog grows at each.

    python3 bench/sweep.py --workload <cell> --rates 8000,12000,16000 \
        --seconds 40 --seed 1

Each rate is one run of ``bench/run.py``'s ``run_cell`` with the cell's
traffic mix at that rate.  A rate is sustained when the backlog does not
grow over the window: it ends at most one published segment
(``segment_records`` events, the grain of the offer) above where it began.
The last stdout line is a JSON object with one row per rate and the
highest sustained rate.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH / "metrics")]

import run  # noqa: E402


def sweep_rate(spec: dict, rate: float, seed: int, seconds: float,
               tmp: str) -> dict:
    traffic = dict(spec["traffic"], offered_rate=rate)
    path = os.path.join(tmp, f"traffic-{int(rate)}.json")
    with open(path, "w") as f:
        json.dump(traffic, f)
    rspec = dict(spec, traffic=traffic, traffic_file=path)
    root = tempfile.mkdtemp(prefix="bench-log-", dir=tmp)
    gen = run.Generator(rspec, seed, root)
    try:
        code, result = run.run_cell(rspec, seed, seconds, False, gen, root)
    finally:
        gen.kill()
        shutil.rmtree(root, ignore_errors=True)
    if code != 0:
        raise SystemExit(code)
    r = result["run"]
    growth = r["backlog_end"] - r["backlog_start"]
    return {"offered_rate": rate, "events_per_s": r["folded"] / seconds,
            "checkpointed_per_s": r["folded_by_checkpoints"] / seconds,
            "backlog_start": r["backlog_start"],
            "backlog_growth": growth,
            "sustained": growth <= int(traffic["segment_records"]),
            "correct": result["correct"],
            "setup_s": result["metrics"]["setup_s"]["value"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    spec = run.load_spec(args.workload)
    run.enable_compile_cache()
    rows = []
    tmp = tempfile.mkdtemp(prefix="bench-sweep-")
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            row = sweep_rate(spec, rate, args.seed + i, args.seconds, tmp)
            print(json.dumps(row), flush=True)
            rows.append(row)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    ok = [r["offered_rate"] for r in rows if r["sustained"]]
    print(json.dumps({"workload": args.workload, "rows": rows,
                      "highest_sustained": max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
