#!/usr/bin/env python3
"""Finds a serving cell's knee: the highest open-loop rate it sustains.

    python bench/sweep.py --workload <serve cell> --rates 4,8,12 --seconds 20

One process sets the cell up once, then offers its mix at each rate in
turn (the pool drained between rates) and prints one JSON line per rate:
requests due, those still without a first token at the window's close,
the waiting queue at a quarter, half, three quarters and the end of the
window, and the TTFT and inter-token tails.  A rate whose queue keeps
growing through the window is past the knee.  The benchmark's own runs
never call this; a cell's mix fixes its rate at about 0.8 x the knee.
"""
import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    sys.path.insert(0, os.path.join(root, "src"))
    from bench import harness, traffic
    from bench.kinds import serve

    spec = harness.read_json(os.path.join(root, "BENCHMARK.json"))
    cell = harness.load_cell(spec, args.workload, args.seed, args.seconds,
                             False)
    devices = harness.find_devices(cell.chips)
    harness.enable_compile_cache()
    srv = serve.setup(cell, devices)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate)
        reqs = traffic.schedule(mix, args.seed, args.seconds,
                                cell.hf["vocab_size"])
        queue = []

        def sample():
            queue.append(len(srv.waiting))

        served = traffic.drive(srv, reqs, args.seconds, drain_s=0.0,
                               marks=[(args.seconds * f, sample)
                                      for f in (0.25, 0.5, 0.75, 1.0)])
        late = sum(1 for r in served.due if not served.tokens[r]
                   or served.tokens[r][0] > args.seconds)
        print(json.dumps({
            "rate_per_s": rate, "due": len(served.due),
            "no_first_token_at_close": late, "waiting": queue,
            "ttft_p90_ms": traffic.percentile(
                [t * 1e3 for t in served.ttfts()], 90),
            "itl_p95_ms": traffic.percentile(
                [t * 1e3 for t in served.itls()], 95),
            "decode_steps": served.n_decode_in_window}), flush=True)
        serve.finish(srv)
    return 0


if __name__ == "__main__":
    sys.exit(main())
