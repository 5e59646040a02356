"""Rate sweep of an open-loop cell: the highest rate it sustains.

    python3 bench/sweep.py --workload splade-seismic.open --seed 7 \
        --rates 10 20 30 30 30 40 --seconds 10 --out sweep.json [--buckets 1 2 4 8 16]

One process holds the chip: it builds the cell's index once, warms
every batch size of the bucket set (the configuration's, or
``--buckets``), and then offers each rate in turn for ``--seconds``
through the same open loop the benchmark uses; a rate given
more than once is offered again in another arrival order. Per rate it
prints the latency quantiles of all requests, how late the generator
ran, how long the queue took to drain after the last request was due,
and the dispatches per bucket. A rate is sustained when the queue
drains within a few service times of the window's end; the cell's
traffic file takes 0.8 x the highest such rate, written as a number.
The table also goes to ``--out`` as JSON.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402  (puts the program on the path)
from bench import corpus, loadgen, spec  # noqa: E402

import numpy as np  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--buckets", type=int, nargs="*")
    ap.add_argument("--out", required=True, help="JSON file for the table")
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    try:
        device = run.device_info(int(cell.workload["chips"]))
    except run.NoChip as e:
        run.log(f"refused: {e}")
        return 2
    from repro.launch import compile_cache

    compile_cache.enable()
    config, traffic = cell.config, dict(cell.traffic)
    p = config["pipeline"]
    corpus_seed, traffic_seed, _ = run.seeds(args.seed)
    counts = [loadgen.n_requests(dict(traffic, rate_qps=r), args.seconds) for r in args.rates]
    corp = corpus.generate(run.profile(config, sum(counts), corpus_seed))
    Q = corp.queries_dense()
    t = time.perf_counter()
    r = run.build(config, corp)
    run.log(f"# build {time.perf_counter() - t:.1f} s; {device}")
    pipe = r.pipeline(buckets=args.buckets or p["buckets"], deadline_us=p["deadline_us"],
                      cache_size=p["cache_size"])
    zeros = np.zeros((max(pipe.plans.buckets), corp.dim), np.float32)
    for n in range(1, len(zeros) + 1):
        t = time.perf_counter()
        np.asarray(pipe.plans.get(pipe.plans.bucket_for(n))(zeros[:n])[0])
        run.log(f"# warm batch of {n} (bucket {pipe.plans.bucket_for(n)}): "
                f"{time.perf_counter() - t:.2f} s")
    rng = np.random.default_rng(traffic_seed)
    rows, lo = [], 0
    for rate, n in zip(args.rates, counts):
        due = loadgen.schedule(dict(traffic, rate_qps=rate), args.seconds, rng)
        before = dict(pipe.stats.dispatches)
        res = loadgen.open_loop(pipe, Q[lo:lo + n], due)
        lo += n
        lat = res["latency_s"]
        row = {
            "rate_qps": rate, "requests": n, "completed": res["completed"],
            "p50_ms": 1e3 * loadgen.nearest_rank(lat, 0.5),
            "p95_ms": 1e3 * loadgen.nearest_rank(lat, 0.95),
            "max_ms": 1e3 * float(lat.max()),
            "gen_lag_p95_ms": 1e3 * loadgen.nearest_rank(res["gen_lag_s"], 0.95),
            "drain_s": res["elapsed_s"] - float(due[-1]),
            "dispatches": {b: c - before.get(b, 0) for b, c in pipe.stats.dispatches.items()},
        }
        rows.append(row)
        run.log(json.dumps(row))
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"workload": args.workload, "seed": args.seed,
                               "device": device, "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
