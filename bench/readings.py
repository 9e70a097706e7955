"""Read the numbers ``correct`` compares, on the chip, for setting limits.

    python3 bench/readings.py --workload granite-3-2b-nomult.block_nocache \
        --seeds 11-22 --controls int8,fp8_kv --control-seeds 31-33 \
        --seconds 20 --out readings.jsonl

Runs the cell once per seed in one process (the chip is held once), at
the cell's own size and load, with a short window; the calls an engine
has started on are served to their end as in a full run, so the sampled
requests it compares are the same as a full run's.  Then it runs the
program's own lower-precision paths (the controls: int8 weights, fp8 KV
cache) on the control seeds.  Each run prints one JSON line of readings; the limits in
``limits/<cell>.json`` are set from them (see PERF.md).  Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402  (puts the program's src on the path)

OPEN = {"wrong_pairs": 0, "query_errors": 0, "unanswered_calls": 0,
        "unchecked_requests": 0,
        "logit_kl_max": float("inf"), "logit_kl_mean": float("inf")}


def seeds(spec: str):
    out = []
    for part in spec.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += range(int(a), int(b) + 1)
        elif part:
            out.append(int(part))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    import jax

    from bench import traffic

    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 3
    man = run.manifest()
    wl = run.find_workload(man, args.workload)
    config = run.load_config(man, wl["config"])
    mix = traffic.load_mix(wl["traffic"])
    jobs = [(None, s) for s in seeds(args.seeds)]
    jobs += [(c, s) for c in args.controls.split(",") if c
             for s in seeds(args.control_seeds)]
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for control, seed in jobs:
        t = time.time()
        line = {"workload": args.workload, "control": control, "seed": seed}
        try:
            res = run.run_cell(config, mix, seed, args.seconds, False,
                               limits=OPEN, control=control)
            line.update(readings=res["readings"], metrics=res["metrics"],
                        device=res["device"])
        except Exception:  # one failed run must not lose the others
            line["error"] = traceback.format_exc()[-3000:]
        line["wall_s"] = time.time() - t
        print(json.dumps(line), flush=True)
        with out.open("a") as f:
            f.write(json.dumps(line) + "\n")
        for a in jax.live_arrays():
            a.delete()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
