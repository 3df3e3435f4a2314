"""Find a serve cell's knee, once, when the cell is defined:

    python3 -m chipbench.sweep --workload <serve cell> --rates 4,6,8,10 --seconds 20 --seed <n>

One warm engine, one window a rate, one JSON line a rate. A rate is
SUSTAINED when the backlog at the window's end is no larger than in its
middle and no request fails. The cell's traffic file then takes 0.8 of
the highest sustained rate, as a number; the harness never searches.
"""

import argparse
import json
import sys
import time

from chipbench import run as harness
from chipbench.common import CompileCounter, cache_everything, require_chips
from chipbench.kinds import serve


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    spec = harness.load_cell(args.workload)
    harness.export_cache_dir()
    import jax

    require_chips(jax, spec["cell"]["chips"])
    cache_everything()
    counter = CompileCounter()
    job = spec["traffic"]
    out_dir = harness.out_dir(spec, args.seed, "sweep")
    occupancy = harness.load_reader("slot_occupancy_pct.serve")
    engine, frontend, _, check = serve.serving(spec["config"], job, args.seed)
    print(json.dumps({"check": check}), flush=True)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            seen = serve.window(
                frontend.address, engine, {**job, "rate_per_s": rate},
                args.seed, args.seconds, out_dir, False, counter)
            client = seen["client"]
            print(json.dumps({
                "rate_per_s": rate, **client,
                "sustained": (client["failed"] == 0 and
                              client["backlog_end"] <= client["backlog_mid"]),
                "slot_occupancy_pct": occupancy(seen),
                "compiles_in_window": seen["compiles_in_window"]}), flush=True)
            # what a rate above the knee left unfinished is served out
            # before the next rate's window opens
            steps = -1
            while steps != engine.stats["steps"]:
                steps = engine.stats["steps"]
                time.sleep(1.0)
    finally:
        frontend.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
