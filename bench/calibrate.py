#!/usr/bin/env python3
"""Readings from which a cell's limits are set (bench/limits/<cell>.json).

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--seconds 8]

For each seed, in one process: the numbers a run compares, read from the
program's timed path at the cell's own size (for serving, after a short
window at the cell's load).  For the first ``--control-seeds`` seeds, also
the same numbers read from the control, the plain reference computed in
float8 (e4m3, one scale per tensor) put in the program's place, and from
planted faults (training: the mean taken over half the batch; serving:
each served token altered).  One JSON line per seed.  The benchmark's own
runs never call this.
"""
import argparse
import dataclasses
import gc
import json
import os
import sys


def train(cell, devices, seeds, emit, n_control):
    from bench.kinds import train as tr
    eng = tr.make_engine(cell)
    progs = {}
    for seed in seeds:
        c = dataclasses.replace(cell, seed=seed)
        state = tr.make_state(c, eng)
        feed = tr.make_feed(c)
        state, progs[seed] = tr.program_readings(c, eng, state, feed)
        feed.close()
        del state
        gc.collect()
    del eng
    gc.collect()
    q = cell.reference().fp8_round
    for i, seed in enumerate(seeds):
        c = dataclasses.replace(cell, seed=seed)
        ref = tr.reference_readings(c)
        rec = {"seed": seed, "program": tr.compare(progs[seed], ref),
               "loss": progs[seed]["loss"], "ref_loss": ref["loss"]}
        if i < n_control:
            rec["control"] = tr.compare(tr.reference_readings(c, quant=q),
                                        ref)
            rec["fault_half_batch"] = tr.compare(
                tr.reference_readings(c, half=True), ref)
        emit(rec)


def serve(cell, devices, seeds, emit, n_control):
    from bench import traffic
    from bench.kinds import serve as sv
    srv = sv.setup(cell, devices)
    samples = {}
    for seed in seeds:
        c = dataclasses.replace(cell, seed=seed)
        srv.params = None
        gc.collect()
        srv.params = c.reference().make_params(c.hf, seed)
        reqs = traffic.schedule(c.mix, seed, c.seconds, c.hf["vocab_size"])
        srv.prompts.clear()
        served = traffic.drive(srv, reqs, c.seconds,
                               min_finished=c.mix["check_requests"])
        samples[seed] = sv.served_sample(c, srv, served)
        sv.finish(srv)
    del srv
    gc.collect()
    for i, seed in enumerate(seeds):
        c = dataclasses.replace(cell, seed=seed)
        gaps = sv.reference_gaps(c, samples[seed], control=i < n_control)
        rec = {"seed": seed, "program": {"served_gap": gaps["served"]},
               "tokens": sum(len(o) for _, o in samples[seed])}
        if i < n_control:
            rec["control"] = {"served_gap": gaps["control"]}
            rec["fault_token"] = {"served_gap": gaps["fault_token"]}
        emit(rec)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/calibrate.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="read the control and faults on this many of "
                         "the seeds, the first ones")
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    sys.path.insert(0, os.path.join(root, "src"))
    from bench import harness

    spec = harness.read_json(os.path.join(root, "BENCHMARK.json"))
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.load_cell(spec, args.workload, seeds[0], args.seconds,
                             False)
    devices = harness.find_devices(cell.chips)
    harness.enable_compile_cache()

    def emit(rec):
        line = json.dumps(dict(rec, workload=cell.name))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    {"train": train, "serve": serve}[cell.mix["kind"]](
        cell, devices, seeds, emit, args.control_seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
