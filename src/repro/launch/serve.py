"""Serving benchmark harness: drives the continuous-batching engine
(runtime/serve.py) over a synthetic workload and reports prefill/decode
throughput plus per-token latency percentiles.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --slots 4 --gen 16
  # plan-sharded pool on a forced-host mesh (solves the decode tiling):
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --reduced \
      --slots 4 --gen 16 --mesh 4x2 --plan auto
  # open-loop Poisson arrivals at 2 req/s:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --requests 12 --arrivals poisson --rate 2.0
  # paged KV pool at half the linear memory + speculative decoding:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --slots 8 --requests 32 --paged --n-blocks 33 --spec-k 4

Only stdlib at module level: --mesh forces the host device count via
XLA_FLAGS, which must be set before jax initializes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="python -m repro.launch.serve")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--gen", type=int, default=16,
                    help="max new tokens per request")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--requests", type=int, default=0,
                    help="number of requests (default: one per slot)")
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk size")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV block pool + block-table cache")
    ap.add_argument("--block-len", type=int, default=16,
                    help="tokens per KV block (must divide --max-len)")
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="pool size in blocks (default: slots * "
                         "max_len/block_len + 1 — linear-equivalent); "
                         "smaller values serve memory-bound via "
                         "preemption")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable radix shared-prefix block reuse")
    ap.add_argument("--spec-k", type=int, default=1,
                    help="self-speculative draft length per dispatch "
                         "(1 = plain decode)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="e.g. 4x2 — forces host devices and builds a "
                         "(data, model) mesh")
    ap.add_argument("--plan", default=None, choices=[None, "auto"],
                    help="'auto' solves the decode tiling for the mesh "
                         "and shards params+cache with it")
    ap.add_argument("--arrivals", default="batch",
                    choices=["batch", "poisson"])
    ap.add_argument("--rate", type=float, default=4.0,
                    help="poisson arrival rate, requests/s")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace of the run "
                         "(admit/prefill/decode/preempt spans)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the run's metrics registry as JSONL "
                         "(TTFT/ITL histograms, pool utilization, "
                         "drift gauges)")
    ap.add_argument("--no-drift", action="store_true",
                    help="skip the predicted-vs-measured wire-byte "
                         "drift check (saves one decode-cell compile; "
                         "drift needs --plan auto)")
    ap.add_argument("--min-decode-tput", type=float, default=None,
                    help="exit non-zero unless decode tok/s exceeds this "
                         "(CI smoke gate)")
    # continuous monitoring (DESIGN.md §17)
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="TTFT SLO target in ms (p95 objective; enables "
                         "the continuous monitor)")
    ap.add_argument("--slo-itl-ms", type=float, default=None,
                    help="inter-token-latency SLO target in ms (p95 "
                         "objective; enables the continuous monitor)")
    ap.add_argument("--flight-dir", default="flight",
                    help="directory for flight-<trigger>.json dumps")
    ap.add_argument("--inject-spike-ms", type=float, default=0.0,
                    help="fault injection: sleep this long after each "
                         "decode step in the injection window (drives "
                         "the CI monitor-smoke breach)")
    ap.add_argument("--inject-at", type=int, default=2,
                    help="decode step the injection window starts at")
    ap.add_argument("--inject-steps", type=int, default=20,
                    help="injection window length in decode steps")
    return ap


def run_workload(srv, arrivals, gen, step_hook=None):
    """Drive the engine over (t_arrival, prompt) pairs; returns the
    metrics record.  Admission and decode are timed separately so the
    prefill/decode split is honest.  ``step_hook(n_decode_steps)`` runs
    after each decode step — the fault-injection point."""
    t0 = time.monotonic()
    pending = sorted(arrivals, key=lambda a: a[0])
    submit_t = {}
    first_tok_t = {}
    tok_times = {}
    prefill_s = decode_s = 0.0
    prompt_toks = decode_toks = 0
    n_decode_steps = 0

    def clock():
        return time.monotonic() - t0

    while pending or srv.waiting or srv.active.any():
        now = clock()
        while pending and pending[0][0] <= now:
            _, prompt = pending.pop(0)
            rid = srv.submit(prompt, max_new_tokens=gen)
            submit_t[rid] = clock()
            tok_times[rid] = []
        if not (srv.waiting or srv.active.any()):
            time.sleep(min(0.001, max(0.0, pending[0][0] - clock())))
            continue

        ta = time.monotonic()
        admit_evs = srv.admit_waiting()
        tb = time.monotonic()
        if srv.scfg.spec_k > 1:
            dec_evs = srv.spec_once()
        else:
            dec_evs = srv.decode_once()
        tc = time.monotonic()
        if admit_evs:
            prefill_s += tb - ta
        if dec_evs:
            decode_s += tc - tb
            n_decode_steps += 1
            if step_hook is not None:
                step_hook(n_decode_steps)
        # prefill-produced tokens are stamped at the end of admission,
        # not after the decode step that happened to follow them —
        # otherwise every TTFT carries one spurious pool decode
        for evs, t, from_decode in ((admit_evs, tb - t0, False),
                                    (dec_evs, tc - t0, True)):
            for kind, rid, val in evs:
                if kind == "admit":
                    prompt_toks += int(srv.prompt_len[val])
                elif kind == "token":
                    first_tok_t.setdefault(rid, t)
                    tok_times[rid].append(t)
                    if from_decode:
                        decode_toks += 1

    total = clock()
    itls = []
    for rid, ts in tok_times.items():
        itls += [b - a for a, b in zip(ts, ts[1:])]
    ttfts = [first_tok_t[r] - submit_t[r] for r in first_tok_t]
    gen_toks = sum(len(ts) for ts in tok_times.values())
    from ..obs import stats
    _pct = stats.percentile
    return {
        "requests": len(tok_times),
        "generated_tokens": gen_toks,
        "prompt_tokens": prompt_toks,
        "wall_s": total,
        "prefill_s": prefill_s,
        "decode_s": decode_s,
        "decode_steps": n_decode_steps,
        "prefill_tok_per_s": (prompt_toks / prefill_s
                              if prefill_s else None),
        "decode_tok_per_s": (decode_toks / decode_s
                             if decode_s else None),
        "total_tok_per_s": gen_toks / total if total else None,
        "ttft_p50_s": _pct(ttfts, 50.0),
        "ttft_p95_s": _pct(ttfts, 95.0),
        "itl_p50_s": _pct(itls, 50.0),
        "itl_p95_s": _pct(itls, 95.0),
        # raw samples, for pooling percentiles across repeated runs
        # (callers serializing this dict should drop them)
        "itl_s": itls,
        "ttft_s": ttfts,
    }


def main(argv=None) -> int:
    ap = build_argparser()
    args = ap.parse_args(argv)
    mesh_shape = None
    if args.plan and not args.mesh:
        ap.error("--plan requires --mesh (the plan shards the pool "
                 "across a mesh)")
    if args.mesh:
        mesh_shape = tuple(int(s) for s in args.mesh.lower().split("x"))
        n_dev = 1
        for s in mesh_shape:
            n_dev *= s
        from ..hostdev import force_host_devices
        force_host_devices(n_dev)

    import jax
    import numpy as np

    from .. import obs
    from ..hostdev import enable_compile_cache
    enable_compile_cache()
    from ..configs.base import ShapeConfig, get_arch
    from ..models.model import LM
    from ..runtime.serve import ServeConfig, Server

    if args.trace_out:
        obs.enable(args.trace_out)
    registry = obs.Registry()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    plan = mesh = None
    plan_rec = None
    if mesh_shape:
        from ..compat import make_compat_mesh
        axis_names = ("data", "model")[:len(mesh_shape)]
        mesh = make_compat_mesh(mesh_shape, axis_names)
        if args.plan == "auto":
            # the same cached solve path the dry-run and conformance
            # cells use (launch/compile.py)
            from ..core.solver import MeshAxis
            from .compile import plan_from_record, solve_cell_plan
            from .mesh import ICI_BW, ICI_LINKS_PER_AXIS
            bw = ICI_BW * ICI_LINKS_PER_AXIS
            axes = [MeshAxis(n, s, bw)
                    for n, s in zip(axis_names, mesh_shape)]
            tag = "r" if args.reduced else ""
            shape = ShapeConfig(
                f"serve{tag}{args.slots}x{args.max_len}",
                args.max_len, args.slots, "decode")
            t0 = time.time()
            plan_rec = solve_cell_plan(cfg, shape, axes,
                                       mesh_name=f"host{args.mesh}")
            plan = plan_from_record(plan_rec)
            print(f"decode plan ({time.time() - t0:.1f}s, cached solve "
                  f"{plan_rec['solve_time']:.1f}s):")
            print(plan.describe())

    model = LM(cfg, plan=plan, mesh=mesh)
    params = model.init(jax.random.PRNGKey(0))
    scfg = ServeConfig(slots=args.slots, max_len=args.max_len,
                       prefill_chunk=args.chunk,
                       temperature=args.temperature, top_k=args.top_k,
                       seed=args.seed, paged=args.paged,
                       block_len=args.block_len, n_blocks=args.n_blocks,
                       prefix_cache=not args.no_prefix_cache,
                       spec_k=args.spec_k)

    # continuous SLO monitor + flight recorder + replan advisor
    # (DESIGN.md §17) — on when any SLO target or fault injection is
    # requested; the unobserved engine pays one attribute check/token
    monitor = recorder = advisor = None
    slos = []
    if args.slo_ttft_ms is not None:
        slos.append(obs.SLO("ttft", target=args.slo_ttft_ms / 1e3))
    if args.slo_itl_ms is not None:
        slos.append(obs.SLO("itl", target=args.slo_itl_ms / 1e3))
    if slos or args.inject_spike_ms:
        recorder = obs.FlightRecorder(args.flight_dir,
                                      registry=registry)
        if args.plan == "auto" and plan_rec is not None:
            from .compile import solve_observed_regime

            def solve_fn(regime, _axes=axes):
                # prefill-heavy is priced over the live prompt shape,
                # decode-heavy over the slot pool at full KV length
                s = (max(args.prompt_len, 8)
                     if regime == "prefill-heavy" else args.max_len)
                return solve_observed_regime(
                    cfg, _axes, f"host{args.mesh}", regime,
                    batch=args.slots, seq_len=s)

            advisor = obs.ReplanAdvisor(solve_fn, plan_rec,
                                        registry=registry)
        monitor = obs.Monitor(slos=slos, registry=registry,
                              recorder=recorder, advisor=advisor)

    srv = Server(model, params, scfg, mesh=mesh, registry=registry,
                 monitor=monitor)

    if monitor is not None:
        # decode- vs prefill-heavy from the live emitted/prompt token
        # mix (this harness admits uniform prompt_len prompts)
        def regime_fn():
            gen = sum(len(v) for v in srv.outputs.values())
            pro = max(1, len(srv.outputs) * args.prompt_len)
            return "decode-heavy" if gen >= pro else "prefill-heavy"

        monitor.regime_fn = regime_fn

    # live mini-calibration (DESIGN.md §16): the plan's as-executed
    # predicted wire bytes vs the compiled decode cell's collectives —
    # the same comparison the CONFORMANCE decode cells declare a band
    # for, emitted as gauges on this run's registry
    drift_rec = None
    if plan is not None and not args.no_drift:
        breakdown = (plan_rec or {}).get("breakdown")
        if breakdown is None:
            print("drift: plan record predates breakdown support "
                  "(stale cache) — skipping")
        else:
            from ..obs import drift as obs_drift
            from .compile import (compile_step, input_specs,
                                  normalize_moe_plan)
            t0 = time.time()
            compiled, _, _ = compile_step(
                cfg, shape, normalize_moe_plan(plan, cfg), mesh,
                input_specs(cfg, shape))
            drift_rec = obs_drift.record_drift(
                registry, breakdown["total"], compiled.as_text(),
                jax.device_count(),
                predicted_by_kind=breakdown.get("by_kind"))
            print(f"drift: predicted "
                  f"{drift_rec['predicted_wire_bytes'] / 1e6:.1f}MB, "
                  f"measured "
                  f"{drift_rec['measured_wire_bytes'] / 1e6:.1f}MB, "
                  f"ratio {drift_rec['ratio']:.2f} "
                  f"(band {drift_rec['band']}, "
                  f"{'in' if drift_rec['in_band'] else 'OUT OF'} band; "
                  f"{time.time() - t0:.1f}s compile)")
            if monitor is not None:
                monitor.check_drift(drift_rec["ratio"],
                                    band=tuple(drift_rec["band"]))

    rng = np.random.default_rng(args.seed)
    n_req = args.requests or args.slots
    prompts = [rng.integers(0, cfg.vocab,
                            size=args.prompt_len).tolist()
               for _ in range(n_req)]
    if args.arrivals == "poisson":
        t_arr = np.cumsum(rng.exponential(1.0 / args.rate, size=n_req))
    else:
        t_arr = np.zeros(n_req)

    # warm the jits (compile time must not pollute the measurement)
    warm = Server(model, params, scfg, mesh=mesh)
    warm.admit(prompts[0], 0, max_new_tokens=2)
    warm.run()
    srv.adopt_jits(warm)
    del warm          # free its param copy + pool cache before measuring

    step_hook = None
    if args.inject_spike_ms:
        lo, hi = args.inject_at, args.inject_at + args.inject_steps

        def step_hook(n):
            if lo <= n < hi:
                time.sleep(args.inject_spike_ms / 1e3)

        print(f"injecting {args.inject_spike_ms:.0f}ms stalls into "
              f"decode steps [{lo}, {hi})")

    rec = run_workload(srv, list(zip(t_arr, prompts)), args.gen,
                       step_hook=step_hook)
    rec["meta"] = {
        "arch": cfg.name, "reduced": args.reduced, "slots": args.slots,
        "max_len": args.max_len, "gen": args.gen,
        "prompt_len": args.prompt_len, "chunk": args.chunk,
        "mesh": args.mesh, "plan": args.plan,
        "arrivals": args.arrivals,
        "rate": args.rate if args.arrivals == "poisson" else None,
        "n_devices": jax.device_count(),
        "paged": args.paged, "spec_k": args.spec_k,
    }
    if args.paged:
        rec["meta"]["block_len"] = args.block_len
        rec["meta"]["n_blocks"] = srv.n_blocks
        rec["meta"]["prefix_cache"] = not args.no_prefix_cache
        rec["paged"] = {
            "prefill_dispatches": srv.prefill_dispatches,
            "decode_dispatches": srv.decode_dispatches,
            "verify_dispatches": srv.verify_dispatches,
            "preemptions": srv.preemptions,
            "prompt_cache_hits": srv.prompt_cache_hits,
        }
    if drift_rec is not None:
        rec["drift"] = drift_rec

    # registry sinks: latency histograms from the workload samples, rate
    # gauges, plus the solver memo-cache counters from the global
    # registry (the solve ran in this process)
    registry.histogram("serve.ttft_s").observe_many(rec["ttft_s"])
    registry.histogram("serve.itl_s").observe_many(rec["itl_s"])
    if rec["decode_tok_per_s"] is not None:
        registry.gauge("serve.decode_tok_per_s").set(
            rec["decode_tok_per_s"])
    if rec["total_tok_per_s"] is not None:
        registry.gauge("serve.total_tok_per_s").set(
            rec["total_tok_per_s"])
    for m in obs.default_registry().collect():
        if m["name"].startswith("solver.") and m["type"] == "counter":
            registry.counter(m["name"]).inc(m["value"])
    if monitor is not None:
        monitor.export_gauges()
        rec["monitor"] = monitor.snapshot()
        rec["monitor"]["flight_dumps"] = recorder.dumps
        rec["monitor"]["advice"] = advisor.advice if advisor else []
        n_breach = sum(1 for e in monitor.events
                       if e["type"] == "slo_breach")
        print(f"monitor: {monitor.n_events} event(s) "
              f"({n_breach} SLO breach obs), "
              f"{len(recorder.dumps)} flight record(s)"
              + "".join(f"\n  flight -> {p}" for p in recorder.dumps))
        for a in (advisor.advice if advisor else []):
            win = a.get("modeled_win")
            print(f"  replan advice [{a['trigger']}/{a['regime']}]: "
                  + (f"error {a['error']}" if "error" in a else
                     f"modeled step {a['current_step_s']:.3g}s -> "
                     f"{a['advised_step_s']:.3g}s "
                     f"(win {win:+.1%}, plan "
                     f"{'changed' if a['plan_changed'] else 'unchanged'})"))
        recorder.close()
    if args.metrics_out:
        registry.dump_jsonl(args.metrics_out)
        print(f"metrics registry -> {args.metrics_out}")
    if args.trace_out:
        obs.export(args.trace_out)
        print(f"trace -> {args.trace_out}")

    def fmt(v, unit=""):
        return "n/a" if v is None else f"{v:,.1f}{unit}"

    print(f"{rec['requests']} requests, "
          f"{rec['generated_tokens']} tokens generated, "
          f"{rec['prompt_tokens']} prompt tokens in "
          f"{rec['wall_s']:.2f}s")
    print(f"  prefill  {fmt(rec['prefill_tok_per_s'], ' tok/s')}  "
          f"({rec['prefill_s']:.2f}s)")
    print(f"  decode   {fmt(rec['decode_tok_per_s'], ' tok/s')}  "
          f"({rec['decode_s']:.2f}s, {rec['decode_steps']} steps)")
    p50 = rec["itl_p50_s"]
    p95 = rec["itl_p95_s"]
    print(f"  latency  per-token p50 "
          f"{fmt(p50 and p50 * 1e3, ' ms')}, p95 "
          f"{fmt(p95 and p95 * 1e3, ' ms')}; ttft p50 "
          f"{fmt(rec['ttft_p50_s'] and rec['ttft_p50_s'] * 1e3, ' ms')}")

    if args.json_out:
        slim = {k: v for k, v in rec.items()
                if k not in ("itl_s", "ttft_s")}
        with open(args.json_out, "w") as f:
            json.dump(slim, f, indent=1)
        print(f"metrics -> {args.json_out}")

    if args.min_decode_tput is not None:
        tput = rec["decode_tok_per_s"] or 0.0
        if tput < args.min_decode_tput:
            print(f"FAIL: decode throughput {tput} < "
                  f"{args.min_decode_tput}")
            return 1
        print(f"decode throughput gate ok "
              f"({tput:.1f} >= {args.min_decode_tput})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
