"""The stream shape a new mix states under ``shape``.

    python3 perfbench/shape.py --config <config> --traffic <mix> [--draws 400]

Draws the mix's stream on the configuration's fleet from ``--draws`` seeds
and prints the most common pair of stream length and expiry-ring width as
the JSON a mix file holds under ``shape``.  The length spreads over some
hundred values, so the pair is one common shape among many, not a typical
one.  Every run of a cell is then handed a seed whose stream has that
shape (:func:`perfbench.lib.stream.program_seed`).  Host only; the benchmark's
own runs do not run this.
"""

import argparse
import collections
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from perfbench.lib import stream  # noqa: E402
from perfbench.lib.fleet import Fleet  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--draws", type=int, default=400)
    ap.add_argument("--first-seed", type=int, default=7_000_000)
    args = ap.parse_args(argv)
    config = json.loads((ROOT / "perfbench" / "configs" / f"{args.config}.json").read_text())
    traffic = json.loads((ROOT / "perfbench" / "traffic" / f"{args.traffic}.json").read_text())
    fleet, sim = Fleet.from_config(config), {**config["sim"], **traffic["sim"]}
    counts = collections.Counter()
    for k in range(args.draws):
        s = stream.presample(fleet, sim, int(traffic["replicas"]), args.first_seed + k,
                             extras=False)
        counts[json.dumps(stream.shape(s))] += 1
    print(counts.most_common(1)[0][0])


if __name__ == "__main__":
    main()
