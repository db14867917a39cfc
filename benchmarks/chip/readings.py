"""The readings each cell's limits are set from (see check.py), on the chip.

For each seed, one run of the cell with a short window gives the program's
numbers; beside them, in the same process, come the numbers of

* the control: the reference one precision below the configuration's
  (bfloat16) put in the program's place;
* the faults the cell can have, planted in the reference put in the
  program's place: half of each batch left out (the mean taken over the
  first half of each negative group), and the sampler's negatives drawn
  from half of the entities only (read by the batch check). A training step
  that returns its state unchanged reads 1 by the change measure and needs
  no run.

One JSON line per seed goes to standard output, then a summary: per number,
the largest reading of the program (the lower reading) and the smallest of
the control and of each fault (the upper readings). The benchmark's own
runs never run this.

    python3 benchmarks/chip/readings.py --workload <cell> --seeds 12 --seconds 1
"""

import argparse
import json
import sys

import run


def train_readings(cell, seed, out):
    import jax.numpy as jnp

    import check
    import reference

    kge = out["layer"]["kge"]
    rd = out["readings"]
    ref, batches = rd["reference"], rd["batches"]
    groups = kge["batch_size"] // kge["neg_group_size"]

    def half(x):  # the first half of each negative group's positives
        return x.reshape(groups, -1)[:, : x.size // groups // 2].ravel()

    halved = [dict(b, h=half(b["h"]), r=half(b["r"]), t=half(b["t"])) for b in batches]
    narrow = [dict(b, neg=b["neg"] // 2) for b in batches]
    return {
        "program": out["numbers"],
        "control": check.train_numbers(reference.train(kge, seed, batches, jnp.bfloat16), ref),
        "half_batch": check.train_numbers(reference.train(kge, seed, halved), ref),
        "negatives_half_range": check.batch_numbers(narrow, rd["train"], kge),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    rows = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        result, out = run.execute(cell, seed, args.seconds, trace=False)
        row = train_readings(cell, seed, out)
        row.update(seed=seed, metrics=result["metrics"])
        rows.append(row)
        print("reading " + json.dumps(row), flush=True)
    summary = {}
    for name in rows[0]["program"]:
        summary[name] = {"lower": max(r["program"][name] for r in rows)}
        for kind in rows[0]:
            if kind not in ("program", "seed", "metrics") and name in rows[0][kind]:
                summary[name][kind] = min(r[kind][name] for r in rows)
    print("summary " + json.dumps({"workload": args.workload, "seeds": len(rows),
                                   "numbers": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
