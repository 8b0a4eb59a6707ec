"""Find the knee of a serving cell once, on the chip: the highest offered
rate at which the backlog does not grow over the window.

    python3 bench/sweep.py --workload serve-qwen3-1.7b-over --rates 1,1.5,2 --seconds 30

One process sets the cell up once and offers each rate in turn (the
traffic file's mix, its rate replaced, fresh rids), and serves every
request due in a window, those waiting at its close after it.  Per rate it prints
the latency median and 90th percentile, the mean number of waiting
requests in the first and the second half of the window, and how long
after the window closed the last request finished.  A rate holds if its
second half waits on no more than a quarter more requests than its
first (plus half a request) and it drains within ``--drain`` seconds;
the knee is the highest rate up to which every rate holds.  With
``--write-rate`` the cell's rate, ``--share`` times the knee, is written
into the traffic file as a number; the benchmark's runs never search.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import harness as H  # noqa: E402
from bench import traffic as T  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--drain", type=float, default=5.0)
    ap.add_argument("--share", type=float, default=0.8)
    ap.add_argument("--write-rate", action="store_true")
    args = ap.parse_args(argv)
    cell = H.find_cell(args.workload)
    try:
        devices = H.require_chips(cell.chips)
    except H.NoChip as e:
        H.log(f"sweep: {e}")
        return 3
    H.enable_compile_cache()
    from bench.drivers import serve as S
    d = S.Driver(cell, args.seed, devices, args.seconds)
    d.drain = True          # every request due is served, however late
    d.setup()
    knee, holding = None, True
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        tr = dict(cell.traffic, rate_per_s=rate)
        d.reqs = T.open_loop(tr, args.seed + i, args.seconds,
                             cell.config["vocab_size"])
        d.rid_base = (i + 1) * 1_000_000
        d.calls = []
        d.window(args.seconds, None)
        lat = d.latency_s * 1e3
        bl = np.asarray(d.backlog) if d.backlog else np.zeros((1, 2))
        first = bl[bl[:, 0] < args.seconds / 2, 1]
        second = bl[(bl[:, 0] >= args.seconds / 2)
                    & (bl[:, 0] < args.seconds), 1]
        drain = float(np.nanmax(d.finish) - d.t_close)
        w1 = first.mean() if first.size else 0.0
        w2 = second.mean() if second.size else 0.0
        holding = holding and w2 <= 1.25 * w1 + 0.5 and drain <= args.drain
        if holding:
            knee = rate
        print(f"rate {rate}: n={lat.size} p50_ms={np.median(lat):.1f} "
              f"p90_ms={np.percentile(lat, 90):.1f} "
              f"waiting_first_half={w1:.2f} waiting_second_half={w2:.2f} "
              f"drain_s={drain:.2f} calls={len(d.calls)} "
              f"{'holds' if holding else 'past the knee'}", flush=True)
    print(f"knee: {knee}")
    if args.write_rate and knee is not None:
        traffic = next(w["traffic"] for w in cell.spec["workloads"]
                       if w["name"] == cell.name)
        path = H.BENCH / "traffic" / f"{traffic}.json"
        data = H.load_json(path)
        data["rate_per_s"] = round(args.share * knee, 3)
        path.write_text(json.dumps(data, indent=2) + "\n")
        print(f"wrote rate_per_s={data['rate_per_s']} into {path.name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
