"""Training benchmark harness: drives the plan-driven training engine
(repro.train) over the synthetic pipeline and reports tokens/s plus a
step-time breakdown.

  # single device, reduced config:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
      --reduced --steps 30
  # solver-plan sharded on a forced-host mesh (cached auto solve),
  # microbatched with int8 error-feedback grad sync:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
      --reduced --steps 30 --mesh 4x2 --plan auto --microbatches 2 \
      --grad-compression --ckpt-dir /tmp/ckpt
  # elastic restart: re-run with --mesh 2x4 and the same --ckpt-dir —
  # the checkpoint reshards onto the new mesh's solved tilings.

Only stdlib at module level: --mesh forces the host device count via
XLA_FLAGS, which must be set before jax initializes.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="python -m repro.launch.train")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers, keeping "
                         "the published widths (sizes a config to the "
                         "memory of fewer chips)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--warmup", type=int, default=2,
                    help="steps excluded from throughput (jit compiles)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="e.g. 4x2 — forces host devices and builds a "
                         "(data, model) mesh")
    ap.add_argument("--plan", default=None, choices=("auto",),
                    help="'auto' solves the train tiling for the mesh "
                         "(cached) and shards params+opt state+batch")
    ap.add_argument("--stages", default=None, metavar="auto|N",
                    help="jointly solve pipeline stage cuts + per-stage "
                         "tilings for the mesh (bubble-aware, n_micro = "
                         "--microbatches) and report the hybrid plan; "
                         "'auto' searches every stage carving, N pins "
                         "the stage count.  The engine run proceeds "
                         "with the flat plan — the stage runner "
                         "(runtime.pipeline_parallel) executes "
                         "homogeneous layer stacks")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--buckets", type=int, default=4)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--no-master-fp32", action="store_true",
                    help="disable the f32 master copy (pure bf16 AdamW)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10,
                    help="device-metric sync interval in steps: losses "
                         "stay on device between boundaries so the host "
                         "never blocks the dispatch pipeline per step")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace of the run "
                         "(data wait / step dispatch / sync / ckpt "
                         "spans)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the run's metrics registry as JSONL "
                         "(step-time breakdown, drift gauges)")
    ap.add_argument("--no-drift", action="store_true",
                    help="skip the predicted-vs-measured wire-byte "
                         "drift check (drift needs --plan auto)")
    ap.add_argument("--min-step-tput", type=float, default=None,
                    help="exit non-zero unless steady-state tokens/s "
                         "exceeds this (CI smoke gate)")
    # continuous monitoring (DESIGN.md §17)
    ap.add_argument("--slo-step-ms", type=float, default=None,
                    help="per-step wall-time SLO target in ms (p95 "
                         "objective; enables the continuous monitor — "
                         "use --log-every 1 for per-step granularity)")
    ap.add_argument("--flight-dir", default="flight",
                    help="directory for flight-<trigger>.json dumps")
    ap.add_argument("--inject-spike-ms", type=float, default=0.0,
                    help="fault injection: stall this long after the "
                         "step dispatch in the injection window")
    ap.add_argument("--inject-at", type=int, default=4,
                    help="step the injection window starts at")
    ap.add_argument("--inject-steps", type=int, default=20,
                    help="injection window length in steps")
    return ap


def main(argv=None) -> int:
    ap = build_argparser()
    args = ap.parse_args(argv)
    mesh_shape = None
    if args.plan and not args.mesh:
        ap.error("--plan requires --mesh")
    if args.stages and not args.mesh:
        ap.error("--stages requires --mesh")
    if args.mesh:
        mesh_shape = tuple(int(s) for s in args.mesh.lower().split("x"))
        n_dev = 1
        for s in mesh_shape:
            n_dev *= s
        from ..hostdev import force_host_devices
        force_host_devices(n_dev)

    import jax

    from ..hostdev import enable_compile_cache
    enable_compile_cache()

    from .. import obs
    from ..configs.base import ShapeConfig, get_arch
    from ..data.pipeline import BatchFeed, DataConfig
    from ..models.model import LM
    from ..optim.adamw import AdamWConfig
    from ..train.engine import EngineConfig, TrainEngine

    if args.trace_out:
        obs.enable(args.trace_out)
    registry = obs.Registry()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers is not None:
        import dataclasses
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    master_fp32 = not args.no_master_fp32

    plan = mesh = None
    plan_rec = None
    if mesh_shape:
        from ..compat import make_compat_mesh
        axis_names = ("data", "model")[:len(mesh_shape)]
        mesh = make_compat_mesh(mesh_shape, axis_names)
        if args.plan == "auto":
            from .compile import plan_from_record, solve_cell_plan
            from .mesh import mesh_to_solver_axes
            axes = mesh_to_solver_axes(mesh)
            tag = "r" if args.reduced else ""
            shape = ShapeConfig(f"train{tag}{args.batch}x{args.seq}",
                                args.seq, args.batch, "train")
            flags = ("_mp" if master_fp32 else "") + \
                ("_ef" if args.grad_compression else "")
            t0 = time.time()
            plan_rec = solve_cell_plan(
                cfg, shape, axes, mesh_name=f"host{args.mesh}{flags}",
                graph_kwargs={"master_fp32": master_fp32,
                              "error_feedback": args.grad_compression})
            plan = plan_from_record(plan_rec)
            print(f"train plan ({time.time() - t0:.1f}s, cached solve "
                  f"{plan_rec['solve_time']:.1f}s):")
            print(plan.describe())
        else:
            print(f"note: --mesh {args.mesh} without --plan auto "
                  f"trains UNSHARDED (no plan, no constraints)")

    pipeline_rec = None
    if args.stages:
        from ..core.builders import build_graph
        from ..core.solver import solve_pipeline
        from .mesh import mesh_to_solver_axes
        p_shape = ShapeConfig(f"stages{args.batch}x{args.seq}",
                              args.seq, args.batch, "train")
        pg = build_graph(cfg, p_shape, master_fp32=master_fp32)
        n_micro = max(1, args.microbatches)
        stage_counts = None if args.stages == "auto" \
            else (1, int(args.stages))
        t0 = time.time()
        psol = solve_pipeline(pg, mesh_to_solver_axes(mesh),
                              n_micro=n_micro,
                              stage_counts=stage_counts, mem_scale=0.0)
        t_flat = psol.candidates.get(1, float("inf"))
        pipeline_rec = {
            "n_stages": psol.n_stages,
            "cuts": psol.cuts,
            "n_micro": n_micro,
            "bubble_factor": psol.bubble_factor,
            "modeled_step_s": psol.total_seconds,
            "flat_step_s": t_flat,
            "candidates_ms": {str(k): v * 1e3
                              for k, v in psol.candidates.items()},
            "solve_s": time.time() - t0,
        }
        print(f"pipeline plan ({pipeline_rec['solve_s']:.1f}s):")
        print("  " + psol.describe().replace("\n", "\n  "))
        if psol.n_stages > 1:
            print(f"  modeled {psol.total_seconds * 1e3:.3f} ms vs best "
                  f"flat {t_flat * 1e3:.3f} ms "
                  f"(x{t_flat / psol.total_seconds:.2f}); this run "
                  f"proceeds with the flat plan (the stage runner "
                  f"executes homogeneous stacks)")
        elif n_micro == 1:
            print("  flat plan wins (with --microbatches 1 the bubble "
                  "factor equals the stage count)")

    model = LM(cfg, plan=plan, mesh=mesh)
    engine = TrainEngine(
        model,
        EngineConfig(microbatches=args.microbatches,
                     buckets=args.buckets,
                     grad_compression=args.grad_compression,
                     master_fp32=master_fp32,
                     optim=AdamWConfig(lr=args.lr,
                                       total_steps=args.steps)),
        mesh=mesh)

    # continuous SLO monitor + flight recorder + replan advisor
    # (DESIGN.md §17) — on when a step SLO or fault injection is
    # requested; the unobserved loop pays one attribute check per step
    monitor = recorder = advisor = None
    slos = []
    if args.slo_step_ms is not None:
        slos.append(obs.SLO("step", target=args.slo_step_ms / 1e3))
    if slos or args.inject_spike_ms:
        recorder = obs.FlightRecorder(args.flight_dir,
                                      registry=registry)
        if args.plan == "auto" and plan_rec is not None:
            from .compile import solve_observed_regime

            def solve_fn(regime, _axes=axes, _flags=flags):
                return solve_observed_regime(
                    cfg, _axes, f"host{args.mesh}{_flags}", regime,
                    batch=args.batch, seq_len=args.seq,
                    graph_kwargs={
                        "master_fp32": master_fp32,
                        "error_feedback": args.grad_compression})

            advisor = obs.ReplanAdvisor(solve_fn, plan_rec,
                                        registry=registry)
        monitor = obs.Monitor(slos=slos, registry=registry,
                              recorder=recorder, advisor=advisor,
                              regime_fn=lambda: "train")

    state = None
    start = 0
    if args.ckpt_dir:
        restored = engine.restore(args.ckpt_dir)
        if restored is not None:
            state, _, start = restored
            print(f"resumed from step {start} "
                  f"({'resharded onto ' + args.mesh if mesh else 'host'})")
    if state is None:
        state = engine.init_state(jax.random.PRNGKey(args.seed))

    # live mini-calibration: the plan's as-executed predicted wire bytes
    # vs the collectives in the engine's OWN compiled step (jax caches
    # the executable, so the training loop below reuses this compile)
    drift_rec = None
    if plan is not None and not args.no_drift:
        breakdown = (plan_rec or {}).get("breakdown")
        if breakdown is None:
            print("drift: plan record predates breakdown support "
                  "(stale cache) — skipping")
        else:
            from ..obs import drift as obs_drift
            from .compile import input_specs
            t0 = time.time()
            compiled = engine.lower_step(input_specs(cfg, shape))
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

    dcfg = DataConfig(seed=args.seed, vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)
    shardings = None
    if mesh is not None and plan is not None:
        shardings = engine.batch_shardings(("tokens", "labels"))

    tokens_per_step = args.batch * args.seq
    warmup = min(args.warmup, max(0, (args.steps - start) - 1))
    log_every = max(1, args.log_every)
    hist = []
    data_s = step_s = ckpt_s = 0.0
    # device metrics are buffered and synced only at flush boundaries
    # (log interval, warmup end, checkpoint, final step) — the old loop
    # forced a device round-trip every step via float(loss), stalling
    # the dispatch pipeline.  The warmup boundary always flushes, so
    # each measured interval is entirely post-warmup.
    pending = []                  # (step, device loss) since last flush
    int_t0 = None                 # wall-clock start of current interval
    int_data = 0.0                # data-wait seconds in current interval
    with BatchFeed(dcfg, start_step=start, shardings=shardings) as feed:
        for step in range(start, args.steps):
            ta = time.monotonic()
            if int_t0 is None:
                int_t0 = ta
            batch = feed.get()
            tb = time.monotonic()
            int_data += tb - ta
            state, metrics = engine.step(state, batch)
            if (args.inject_spike_ms
                    and args.inject_at <= step - start
                    < args.inject_at + args.inject_steps):
                time.sleep(args.inject_spike_ms / 1e3)
            pending.append((step, metrics["loss"]))

            at_ckpt = (args.ckpt_dir
                       and (step + 1) % args.ckpt_every == 0)
            flush = ((step + 1 - start) % log_every == 0
                     or step - start == warmup - 1
                     or step == args.steps - 1 or at_ckpt)
            if not flush:
                continue
            ts0 = time.monotonic()
            with obs.span("train.sync", steps=len(pending)):
                jax.block_until_ready(pending[-1][1])
            tc = time.monotonic()
            int_wall = tc - int_t0
            sec_each = int_wall / len(pending)
            measured = pending[0][0] - start >= warmup
            if monitor is not None and measured:
                # per-step wall time (exact per step at --log-every 1),
                # amortized data wait, and the device-sync straggler
                # signal — the streams the burn-rate and MAD-z rules run
                for _ in pending:
                    monitor.observe("step", sec_each)
                monitor.observe("data_wait", int_data / len(pending))
                monitor.observe("sync", tc - ts0)
            if measured:
                data_s += int_data
                step_s += int_wall - int_data
                registry.histogram("train.step_s").observe(
                    sec_each - int_data / len(pending))
            for s, dev_loss in pending:
                hist.append({"step": s, "loss": float(dev_loss),
                             "sec": sec_each})
            loss = hist[-1]["loss"]
            pending = []
            int_t0 = None
            int_data = 0.0
            if at_ckpt:
                engine.save(args.ckpt_dir, step + 1, state,
                            extra={"loss": loss})
                from ..checkpoint import ckpt
                ckpt.gc_old(args.ckpt_dir)
                ckpt_s += time.monotonic() - tc

    n_meas = max(1, len(hist) - warmup)
    mean_step = step_s / n_meas
    tput = tokens_per_step / mean_step if step_s else 0.0
    rec = {
        "meta": {
            "arch": cfg.name, "reduced": args.reduced,
            "n_layers": cfg.n_layers,
            "batch": args.batch, "seq": args.seq,
            "steps": len(hist), "microbatches": args.microbatches,
            "buckets": args.buckets,
            "grad_compression": args.grad_compression,
            "master_fp32": master_fp32,
            "mesh": args.mesh, "plan": args.plan,
            "stages": args.stages,
            "n_devices": jax.device_count(),
        },
        "first_loss": hist[0]["loss"] if hist else None,
        "last_loss": hist[-1]["loss"] if hist else None,
        "tokens_per_step": tokens_per_step,
        "mean_step_s": mean_step,
        "tokens_per_s": tput,
        "breakdown_s": {"data": data_s, "step": step_s, "ckpt": ckpt_s},
        "losses": [h["loss"] for h in hist],
        "predicted_wire_bytes": (plan_rec or {}).get("total_bytes"),
        "drift": drift_rec,
        "pipeline": pipeline_rec,
    }
    if hist:
        print(f"{len(hist)} steps, loss {rec['first_loss']:.3f} -> "
              f"{rec['last_loss']:.3f}")
        print(f"  throughput {tput:,.1f} tok/s "
              f"(mean step {mean_step * 1e3:.1f} ms over {n_meas} steps)")
    else:
        print(f"nothing to do: resumed at step {start} >= "
              f"--steps {args.steps}")
    print(f"  breakdown  data {data_s:.2f}s | step {step_s:.2f}s | "
          f"ckpt {ckpt_s:.2f}s")

    if monitor is not None:
        monitor.export_gauges()
        rec["monitor"] = monitor.snapshot()
        rec["monitor"]["flight_dumps"] = recorder.dumps if recorder else []
        rec["monitor"]["advice"] = advisor.advice if advisor else []
        n_breach = sum(1 for e in monitor.events
                       if e["type"] == "slo_breach")
        print(f"  monitor: {monitor.n_events} event(s) "
              f"({n_breach} SLO breach obs), "
              f"{len(rec['monitor']['flight_dumps'])} flight dump(s)")
        for a in rec["monitor"]["advice"]:
            if "error" in a:
                print(f"  replan advice [{a['trigger']}/{a['regime']}]: "
                      f"solve failed: {a['error']}")
                continue
            print(f"  replan advice [{a['trigger']}/{a['regime']}]: "
                  f"modeled step {a['current_step_s']:.2e}s -> "
                  f"{a['advised_step_s']:.2e}s "
                  f"(win {a['modeled_win'] * 100:+.1f}%, "
                  f"{'plan changed' if a['plan_changed'] else 'same plan'})")
        if recorder is not None:
            recorder.close()

    # registry sinks: step-time breakdown gauges (the train.step_s
    # histogram was fed per measured interval in the loop), throughput,
    # plus the solver memo-cache counters from the global registry
    registry.gauge("train.tokens_per_s").set(tput)
    registry.gauge("train.mean_step_s").set(mean_step)
    registry.gauge("train.data_s").set(data_s)
    registry.gauge("train.step_total_s").set(step_s)
    registry.gauge("train.ckpt_s").set(ckpt_s)
    for m in obs.default_registry().collect():
        if m["name"].startswith("solver.") and m["type"] == "counter":
            registry.counter(m["name"]).inc(m["value"])
    if args.metrics_out:
        registry.dump_jsonl(args.metrics_out)
        print(f"metrics registry -> {args.metrics_out}")
    if args.trace_out:
        obs.export(args.trace_out)
        print(f"trace -> {args.trace_out}")

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"metrics -> {args.json_out}")

    if args.min_step_tput is not None:
        if not hist:
            print("step throughput gate skipped (no steps ran)")
            return 0
        if tput < args.min_step_tput:
            print(f"FAIL: step throughput {tput} < {args.min_step_tput}")
            return 1
        print(f"step throughput gate ok "
              f"({tput:.1f} >= {args.min_step_tput})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
