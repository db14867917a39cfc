"""``readings.py`` for the cells of the ``train_proj`` entry: the same
readings and summary, with ``reference_transr.py`` as the reference the
control and the half-batch fault are computed by.

    python3 benchmarks/chip/readings_proj.py --workload <cell> --seeds 12 --seconds 1
"""

import sys

import readings


def train_readings(cell, seed, out):
    import jax.numpy as jnp

    import check
    import reference_transr

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
        "control": check.train_numbers(
            reference_transr.train(kge, seed, batches, jnp.bfloat16), ref),
        "half_batch": check.train_numbers(reference_transr.train(kge, seed, halved), ref),
        "negatives_half_range": check.batch_numbers(narrow, rd["train"], kge),
    }


if __name__ == "__main__":
    readings.train_readings = train_readings
    sys.exit(readings.main())
