"""Command-line drivers, mirroring the reference's CLI surface.

``tdn up``     — orchestrator (run_grpc_fcnn.py:347-363: ``--config --inputs``)
``tdn infer``  — client (run_grpc_inference.py:218-252:
                 ``[input_index] --inputs --port --timeout --batch-size``;
                 ``--port``/``--timeout`` are accepted for drop-in
                 compatibility but are no-ops — there are no sockets in
                 the data path)
``tdn train``  — the native training path (subsumes the reference's
                 offline scripts/generate_mnist_*.py + notebook recipes)
``tdn oracle`` — scripts/manual_nn.py analogue: single-process float64
                 forward with per-example latency printout
``tdn router`` — multi-replica front door: load-aware gRPC router over
                 an engine replica pool (p2c placement, session
                 affinity, failover, rolling restarts; docs/SCALING.md)
``tdn metrics``— one-shot scrape/pretty-print of a ``--metrics-port``
                 /metrics endpoint (obs/exposition.py); ``--aggregate``
                 folds a router's whole fleet into one view
``tdn trace``  — pull a ``--metrics-port`` endpoint's recorded request
                 spans as a Chrome trace-event file (obs/trace.py);
                 the output opens directly in Perfetto/chrome://tracing
``tdn profile``— pull the per-stage self-time breakdown (obs/profile.py
                 via ``GET /profile``) as a "where does the time go"
                 table, optionally with an on-demand ``jax.profiler``
                 device capture (``GET /debug/profile``)
``tdn top``    — live fleet dashboard (obs/top.py): per-replica rps,
                 percentiles, slots, breaker state, SLO budget, and
                 sparklines over a router (or single-server) endpoint
``tdn incident``— browse the flight recorder's anomaly/crash-triggered
                 diagnostic bundles (obs/incident.py): ls | show ID |
                 pull ID against a --metrics-port endpoint started
                 with --incident-dir
``tdn debug``  — on-demand diagnostic capture (``tdn debug bundle``):
                 GET /debug/bundle and save the zip; against a router
                 the capture spans the whole fleet with the traces
                 stitched
``tdn lint``   — machine-checked project invariants (tools/tdnlint):
                 lock discipline, tick purity, metric-series
                 lifecycle, admin actuation, jit purity — exit 1 on
                 any non-baselined finding (docs/STATIC_ANALYSIS.md)
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time

import numpy as np

logging.basicConfig(
    level=logging.INFO, format="%(asctime)s - %(levelname)s - %(message)s"
)
log = logging.getLogger("tpu_dist_nn.cli")


def _add_multihost_args(p):
    p.add_argument("--coordinator",
                   help="multi-host: coordinator address host:port "
                        "(jax.distributed over DCN); every host runs "
                        "the same command")
    p.add_argument("--num-hosts", type=int, default=None)
    p.add_argument("--host-id", type=int, default=None)


def _init_multihost(args) -> None:
    """Join the multi-process job BEFORE any backend use (multihost.py
    notes why ordering matters).

    Only runs for subcommands that registered the multihost args —
    oracle/import-torch never touch JAX and must not initialize the
    backend (on a TPU host, libtpu acquisition is exclusive). Without
    ``--coordinator`` or a multi-host environment nothing is called at
    all.
    """
    if not hasattr(args, "coordinator"):
        return
    from tpu_dist_nn.parallel.multihost import (
        initialize_multihost,
        multihost_environment,
    )

    if args.coordinator is None:
        if args.num_hosts is not None or args.host_id is not None:
            raise ValueError(
                "--num-hosts/--host-id require --coordinator (without it "
                "this process would silently train single-host)"
            )
        if not multihost_environment():
            return  # plain single-host run: touch nothing
    topo = initialize_multihost(args.coordinator, args.num_hosts, args.host_id)
    if topo.is_multihost:
        log.info(
            "multi-host job: process %d/%d, %d local / %d global devices",
            topo.process_id, topo.num_processes,
            topo.local_device_count, topo.global_device_count,
        )


def _validate_checkpoint_flags(args) -> None:
    """Fail flag-combination errors BEFORE data loading / Engine.up
    (which is expensive on real hardware)."""
    if not getattr(args, "checkpoint_dir", None):
        return  # no manager will be built; flags are inert
    if getattr(args, "checkpoint_format", "native") != "orbax":
        return
    if args.async_checkpoints:
        raise ValueError(
            "--async-checkpoints is the native store's writer; Orbax "
            "has its own async pipeline (drop the flag)"
        )
    try:
        import orbax.checkpoint  # noqa: F401
    except ImportError as e:
        raise ValueError(
            f"--checkpoint-format orbax needs orbax installed ({e}); "
            "pip install orbax-checkpoint"
        ) from e


def _make_checkpoint_manager(args):
    if args.checkpoint_format == "orbax":
        from tpu_dist_nn.checkpoint.orbax_store import OrbaxCheckpointManager

        return OrbaxCheckpointManager(
            args.checkpoint_dir, keep=args.keep_checkpoints
        )
    from tpu_dist_nn.checkpoint import AsyncCheckpointManager, CheckpointManager

    manager = AsyncCheckpointManager if args.async_checkpoints else CheckpointManager
    return manager(args.checkpoint_dir, keep=args.keep_checkpoints)


def _validate_metrics_out(args) -> None:
    """Fail a bad --metrics-out path BEFORE training, not after hours
    of work (same up-front convention as _validate_checkpoint_flags).
    Probes with a real append-open, so directory targets, permission
    problems, and missing parents all surface now."""
    path = getattr(args, "metrics_out", None)
    if not path:
        return
    try:
        with open(path, "a"):
            pass
    except OSError as e:
        raise ValueError(f"--metrics-out path is not writable: {e}") from e


def _write_metrics_jsonl(path, records) -> None:
    """One JSON object per line — the structured metrics channel
    (SURVEY.md §5 metrics: the reference only printed; this persists).

    Appends with a ``{"run": "begin"}`` marker per invocation, so a
    checkpoint-resumed rerun pointed at the same path extends the
    earlier invocation's records instead of overwriting them (markers
    keep the per-invocation lineage readable as one stream).

    Multi-host: process 0 only — concurrent writes to a shared path
    would interleave, and per-host records would cover only that
    host's data stripe.
    """
    import jax

    if jax.process_index() != 0:
        return
    with open(path, "a") as f:
        f.write(json.dumps({"run": "begin"}) + "\n")
        for r in records:
            f.write(json.dumps(r) + "\n")
    log.info("wrote %d metric records to %s", len(records), path)


def _jax_process_count() -> int:
    import jax

    return jax.process_count()


# Live (server, sampler) pairs, drained by main()'s finally so an
# error path anywhere in a command cannot leak a bound port or a
# sampler thread into an in-process caller (tests run main() directly).
_live_metrics_servers: list = []


def _start_metrics_server(args, health_fn=None, routes=None,
                          post_routes=None):
    """Start the /metrics + /healthz endpoint when --metrics-port was
    passed; prints the bound port as a JSON line (``port=0`` picks an
    ephemeral one — drivers/tests read the line, the reference's
    port-in-stdout convention). Returns the server or None. A busy
    port is a user error (ValueError -> clean rc 2), not a traceback."""
    port = getattr(args, "metrics_port", None)
    if port is None:
        return None
    if _jax_process_count() > 1:
        import jax

        if jax.process_index() != 0:
            # One exposition endpoint per job: every host binding the
            # same port on shared infra would collide, and per-host
            # counters would cover only that host's stripe.
            return None
    from tpu_dist_nn.obs import start_http_server

    try:
        server = start_http_server(port, health_fn=health_fn, routes=routes,
                                   post_routes=post_routes)
    except OSError as e:
        raise ValueError(f"--metrics-port {port} could not bind: {e}") from e
    _live_metrics_servers.append([server, None])
    print(json.dumps({"metrics_port": server.port}), flush=True)
    return server


def _attach_metrics_sampler(server, sampler) -> None:
    for entry in _live_metrics_servers:
        if entry[0] is server:
            entry[1] = sampler


def _stop_metrics_server(server, sampler=None) -> None:
    if sampler is not None:
        sampler.stop()
    if server is not None:
        server.close()
        _live_metrics_servers[:] = [
            e for e in _live_metrics_servers if e[0] is not server
        ]


def _drain_metrics_servers() -> None:
    """Close anything a command's error path left running (close() is
    idempotent, so the normal-path _stop_metrics_server calls and this
    sweep compose)."""
    for server, sampler in list(_live_metrics_servers):
        _stop_metrics_server(server, sampler)


def _add_slo_args(p) -> None:
    """The SLO flags shared by every serving verb (up/lm/router):
    declaring an objective turns on the burn-rate tracker over the
    endpoint's time-series ring (docs/OBSERVABILITY.md 'SLOs & burn
    rate')."""
    p.add_argument("--slo-latency-p99-ms", type=float, default=None,
                   metavar="MS",
                   help="latency objective: p99 of this command's "
                        "request-latency histogram must stay <= MS. "
                        "Exports tdn_slo_burn_rate{window=fast|slow} / "
                        "tdn_slo_error_budget_remaining, serves GET "
                        "/slo, and emits rate-limited slo.burn events "
                        "while the fast window burns > 1.0")
    p.add_argument("--slo-availability", type=float, default=None,
                   metavar="FRACTION",
                   help="availability objective in (0, 1), e.g. 0.999: "
                        "at least this fraction of requests must "
                        "succeed (same exports as the latency SLO)")


def _validate_slo_flags(args, needs: str | None = None) -> None:
    """Fail bad SLO flags BEFORE engine bring-up (the file's fail-fast
    convention). ``needs`` names an additional flag attribute the SLO
    tracker rides on for this command (e.g. serving must actually be
    enabled) — without it the flags would be silently inert."""
    lat = getattr(args, "slo_latency_p99_ms", None)
    if lat is not None and lat <= 0:
        raise ValueError(
            f"--slo-latency-p99-ms must be > 0, got {lat}"
        )
    avail = getattr(args, "slo_availability", None)
    if avail is not None and not 0.0 < avail < 1.0:
        raise ValueError(
            f"--slo-availability must be in (0, 1), got {avail} "
            "(e.g. 0.999 for three nines)"
        )
    if lat is None and avail is None:
        return
    if getattr(args, "metrics_port", None) is None:
        raise ValueError(
            "--slo-latency-p99-ms/--slo-availability need "
            "--metrics-port: the SLO tracker rides the runtime "
            "sampler and serves GET /slo there"
        )
    if needs is not None and getattr(args, needs.replace("-", "_"),
                                     None) is None:
        raise ValueError(
            f"--slo-latency-p99-ms/--slo-availability need --{needs} "
            "on this command (no serving path, nothing to measure)"
        )


def _wire_fleet_obs(args, metrics_server, sampler, *, latency_family,
                    latency_match=None, availability_kwargs=None,
                    scheduler=None):
    """Attach the fleet-observability plane to one serving command:
    a time-series ring sampled every tick (GET /timeseries), the
    goodput tracker's MFU/pad gauge tick + GET /goodput, plus — when
    SLO flags were passed — the burn-rate tracker (GET /slo, tdn_slo_*
    gauges, slo.burn events). ``scheduler`` (the server's batcher /
    continuous scheduler) additionally closes the degradation-ladder
    loop: an AdmissionGovernor maps the tracker's fast-burn verdict to
    admission pressure, one SLO class at a time
    (docs/ROBUSTNESS.md "Degradation ladder"). Returns (ring,
    tracker)."""
    if metrics_server is None or sampler is None:
        return None, None
    from tpu_dist_nn.obs.goodput import GOODPUT
    from tpu_dist_nn.obs.slo import (
        SLOTracker,
        availability_objective,
        latency_objective,
    )
    from tpu_dist_nn.obs.timeseries import TimeSeriesRing

    ring = TimeSeriesRing()
    # Goodput ticks BEFORE the ring collects (runtime.py ordering), so
    # /timeseries records this tick's tdn_mfu_ratio.
    sampler.add_goodput(GOODPUT)
    sampler.add_timeseries(ring)
    objectives = []
    lat = getattr(args, "slo_latency_p99_ms", None)
    if lat is not None:
        objectives.append(latency_objective(
            "request_latency_p99", latency_family, lat / 1000.0,
            q=0.99, match=latency_match,
        ))
    avail = getattr(args, "slo_availability", None)
    if avail is not None:
        objectives.append(availability_objective(
            "availability", avail, **(availability_kwargs or {}),
        ))
    tracker = None
    if objectives:
        tracker = SLOTracker(ring, objectives)
        sampler.add_slo_tracker(tracker)
        core = getattr(scheduler, "_core", None) or getattr(
            scheduler, "_sched_core", None
        )
        if core is not None:
            from tpu_dist_nn.serving.sched_core import AdmissionGovernor

            # Burn-rate tightening: sustained fast burn > 1 sheds
            # best_effort admission first, then standard; sustained
            # calm releases one class at a time.
            sampler.add_admission_governor(
                AdmissionGovernor(tracker, [core])
            )
    metrics_server.attach(timeseries=ring, slo=tracker, goodput=GOODPUT)
    return ring, tracker


def _add_incident_args(p) -> None:
    """The flight-recorder flags shared by every serving verb
    (up/lm/router): an incident directory arms the detectors
    (docs/OBSERVABILITY.md 'Incidents & flight recorder')."""
    p.add_argument("--incident-dir", default=None, metavar="DIR",
                   help="arm the flight recorder: anomaly detectors "
                        "(SLO fast burn, error/shed spikes, breaker "
                        "opens, drain/failover on a router) run on the "
                        "runtime-sampler tick and snapshot a diagnostic "
                        "bundle zip (trace ring, /profile, /timeseries "
                        "window, log ring, /slo, /metrics, manifest) "
                        "into DIR on trigger; crashes (unhandled "
                        "exception, SIGABRT) capture too. Costs the "
                        "request path nothing until a detector fires. "
                        "Needs --metrics-port (the detectors ride the "
                        "sampler)")
    p.add_argument("--incident-max", type=int, default=20, metavar="N",
                   help="keep at most N incident bundles in "
                        "--incident-dir; the oldest are pruned "
                        "(default 20)")
    p.add_argument("--incident-cooldown", type=float, default=300.0,
                   metavar="SECONDS",
                   help="minimum spacing between captures of the SAME "
                        "detector (default 300); an ongoing incident "
                        "re-captures after the cooldown, a flapping "
                        "one cannot fill the store")


def _validate_incident_flags(args, needs: str | None = None) -> None:
    """Fail bad flight-recorder flags BEFORE engine bring-up (the
    file's fail-fast convention). ``needs`` names the serving flag the
    recorder rides on for this command (the _validate_slo_flags
    contract) — without it the flags would be silently inert."""
    if getattr(args, "incident_max", 20) < 1:
        raise ValueError(
            f"--incident-max must be >= 1, got {args.incident_max}"
        )
    if getattr(args, "incident_cooldown", 300.0) <= 0:
        raise ValueError(
            f"--incident-cooldown must be > 0, got "
            f"{args.incident_cooldown}"
        )
    if getattr(args, "incident_dir", None) is None:
        return
    if getattr(args, "metrics_port", None) is None:
        raise ValueError(
            "--incident-dir needs --metrics-port: the detectors ride "
            "the runtime sampler and the bundles are served from "
            "GET /incidents there"
        )
    if needs is not None and getattr(args, needs.replace("-", "_"),
                                     None) is None:
        raise ValueError(
            f"--incident-dir needs --{needs} on this command (no "
            "serving path, nothing to record)"
        )


def _wire_incident_recorder(args, metrics_server, sampler, ring, tracker,
                            *, pool=None, router=False):
    """Attach the flight recorder to one serving command: mounts the
    incident surface (/incidents, /incidents/get, and — on a router —
    the fleet-capturing /debug/bundle) on the metrics endpoint, and,
    when ``--incident-dir`` armed it, registers the detector pass on
    the sampler tick plus the crash hooks. Returns the recorder (or
    None without a metrics endpoint)."""
    if metrics_server is None or sampler is None:
        return None
    from tpu_dist_nn.obs.incident import (
        FlightRecorder,
        IncidentStore,
        default_detectors,
        incident_routes,
        install_crash_hook,
    )

    store = None
    detectors = ()
    if getattr(args, "incident_dir", None):
        store = IncidentStore(args.incident_dir,
                              max_incidents=args.incident_max)
        detectors = default_detectors(router=router)
    recorder = FlightRecorder(
        store, detectors=detectors, ring=ring, slo=tracker, pool=pool,
        cooldown=getattr(args, "incident_cooldown", 300.0),
    )
    # The surface mounts even disarmed: /debug/bundle on-demand capture
    # (fleet-wide on a router) costs nothing at rest, and /incidents
    # 404s with the --incident-dir hint.
    metrics_server.add_routes(incident_routes(recorder))
    if store is not None:
        sampler.add_incident_recorder(recorder)
        install_crash_hook(recorder)
        print(json.dumps({
            "incident_dir": store.directory,
            "incident_max": store.max_incidents,
            "incident_detectors": [
                getattr(d, "name", type(d).__name__) for d in detectors
            ],
        }), flush=True)
    return recorder


def _validate_autoscale_flags(args) -> None:
    """Fail bad autopilot flags BEFORE fleet bring-up (the file's
    fail-fast convention). Autoscaling rides the runtime sampler, so
    --metrics-port is required; a spawner exists only with --config
    (static fleets still get scale-DOWN + the manual override)."""
    amin = getattr(args, "autoscale_min", None)
    amax = getattr(args, "autoscale_max", None)
    if (amin is None) != (amax is None):
        raise ValueError(
            "--autoscale-min and --autoscale-max must be passed "
            "together (the bounds define the policy's envelope)"
        )
    if amin is None:
        return
    if not 1 <= amin <= amax:
        raise ValueError(
            f"need 1 <= --autoscale-min <= --autoscale-max, got "
            f"{amin}..{amax}"
        )
    target = getattr(args, "autoscale_target_occupancy", 0.6)
    if not 0.0 < target <= 1.5:
        raise ValueError(
            f"--autoscale-target-occupancy must be in (0, 1.5], got "
            f"{target}"
        )
    if getattr(args, "metrics_port", None) is None:
        raise ValueError(
            "--autoscale-min/--autoscale-max need --metrics-port: the "
            "control loop runs on the runtime sampler's tick and the "
            "POST /router/scale override is served there"
        )


def _validate_hedge_flags(args) -> None:
    ratio = getattr(args, "hedge_after_p99_ratio", None)
    if ratio is not None and ratio <= 0:
        raise ValueError(
            f"--hedge-after-p99-ratio must be > 0, got {ratio}"
        )
    if getattr(args, "hedge_generate", False) and ratio is None:
        raise ValueError(
            "--hedge-generate needs --hedge-after-p99-ratio (it only "
            "opts Generate into the hedging the ratio enables)"
        )


def _apply_trace_sample_rate(args) -> None:
    """Configure the process tracer's head-sampling rate from
    ``--trace-sample-rate`` (fail-fast: an out-of-range rate is a user
    error before any expensive bring-up). Unset leaves the tracer
    default (1.0, or TDN_TRACE_SAMPLE_RATE)."""
    rate = getattr(args, "trace_sample_rate", None)
    if rate is None:
        return
    from tpu_dist_nn.obs import TRACER

    try:
        TRACER.configure(sample_rate=rate)
    except ValueError as e:
        raise ValueError(f"--trace-sample-rate: {e}") from e


def _parse_distribution(text):
    if text is None:
        return None
    return [int(t) for t in text.replace(",", " ").split()]


def _add_up_args(p, config_required=True):
    p.add_argument("--config", required=config_required, help="model JSON file")
    p.add_argument("--inputs", help="example inputs JSON file")
    p.add_argument("--distribution", help="layer distribution, e.g. 1,1,1")
    p.add_argument("--data-parallel", type=int, default=1)
    p.add_argument("--microbatches", type=int, default=4)
    p.add_argument("--quantize", choices=["int8"],
                   help="serve through the fused int8 kernel "
                        "(dense single-chip only)")
    p.add_argument("--virtual-stages", type=int, default=1,
                   help="interleaved (virtual-stage) inference placement: "
                        "the distribution's V entries become V pipeline "
                        "chunks on V/v devices, chunk c on device c %% "
                        "(V/v) (Megatron placement, table-driven forward)")


def _engine_from_args(args, warmup=True):
    from tpu_dist_nn.api.engine import Engine

    return Engine.up(
        args.config,
        _parse_distribution(getattr(args, "distribution", None)),
        data_parallel=getattr(args, "data_parallel", 1),
        num_microbatches=getattr(args, "microbatches", 4),
        warmup=warmup,
        quantize=getattr(args, "quantize", None),
        virtual_stages=getattr(args, "virtual_stages", 1),
    )


def _serve_loop(engine, max_seconds: float | None = None, teardown=None,
                stop_event=None) -> None:
    """Supervisor loop: stay up until SIGINT, then tear down cleanly —
    the reference orchestrator's main loop (run_grpc_fcnn.py:326-344).
    ``max_seconds`` bounds the loop for tests. ``teardown`` overrides
    the default ``engine.down()`` (the gRPC path must drain the server
    BEFORE downing the engine, or grace-period requests hit a dead
    engine). ``stop_event`` ends the loop early — the graceful-drain
    path sets it once SIGTERM has drained in-flight work."""
    t0 = time.monotonic()
    try:
        while max_seconds is None or time.monotonic() - t0 < max_seconds:
            if stop_event is not None and stop_event.wait(0.2):
                break
            if stop_event is None:
                time.sleep(0.2)
    except KeyboardInterrupt:
        log.info("interrupt received; tearing down")
    finally:
        if teardown is not None:
            teardown()
        else:
            engine.down()
        log.info("engine down; relaunch with `tdn up` (stateless restart)")


def cmd_up(args) -> int:
    _apply_trace_sample_rate(args)
    _validate_slo_flags(args, needs="grpc-port")
    _validate_incident_flags(args, needs="grpc-port")
    if args.grpc_port is not None and _jax_process_count() > 1:
        # Before engine bring-up: minutes of pod warmup for a flag
        # combination knowable up front.
        raise ValueError(
            "--grpc-port is single-host only: an RPC landing on one "
            "host would dispatch collectives the other hosts never "
            "join (deadlock); serve from a single-process engine"
        )
    # Bind /metrics + /healthz BEFORE the (expensive) engine bring-up:
    # a busy port must fail in seconds, not after minutes of pod
    # warmup (the file's fail-fast convention). The health closure
    # late-binds `engine`; until it exists /healthz reports not-ready
    # 503 — which is exactly what bring-up IS. probe=False: a per-
    # request device probe from the HTTP thread would race the serving
    # path and pay an XLA compile on the poller's first hit. The drain
    # controller wraps the closure so SIGTERM flips /healthz to
    # NOT_SERVING the instant draining starts (load balancers must
    # stop routing before the port refuses).
    from tpu_dist_nn.serving.resilience import GracefulDrain

    drain = GracefulDrain(grace_seconds=args.drain_grace_seconds)
    metrics_server = _start_metrics_server(
        args, health_fn=drain.wrap_health(
            lambda: engine.health(probe=False)
        )
    )
    sampler = None
    engine = _engine_from_args(args)
    from tpu_dist_nn.utils.backend import device_memory, device_report

    print(json.dumps({"ready": True, "setup_seconds": engine.setup_seconds,
                      "placement": engine.placement(),
                      "device": device_report(),
                      "device_memory": device_memory()}), flush=True)
    if args.inputs:
        from tpu_dist_nn.core.schema import load_examples

        x, y = load_examples(args.inputs)
        result = engine.run_inference(x[:1])
        print(json.dumps({"smoke_inference": result.outputs[0].tolist()}))
    if args.probe_latency:
        print(json.dumps({"step_latency": engine.step_latency()}))
    if args.grpc_port is not None:
        from tpu_dist_nn.serving import serve_engine

        # warm_rows precompiles the request-coalescing bucket shapes so
        # the first concurrent burst doesn't pay XLA compiles mid-flight.
        server, bound = serve_engine(
            engine, args.grpc_port, warm_rows=args.serve_warm_rows,
            max_pending_rows=args.max_pending_rows,
            class_watermarks=_parse_class_watermarks(
                getattr(args, "class_watermarks", None)
            ),
        )
        # SIGTERM → drain: healthz NOT_SERVING, stop accepting, finish
        # in-flight within --drain-grace-seconds, then exit the loop.
        drain.add_server(server)
        drain.install_signal_handler()
        print(json.dumps({"grpc_port": bound}), flush=True)
        if metrics_server is not None:
            from tpu_dist_nn.obs import RuntimeSampler, TRACER

            sampler = RuntimeSampler()
            if server.batcher is not None:
                sampler.add_batcher(server.batcher, method="Process")
            sampler.add_engine(engine)
            sampler.add_tracer(TRACER)
            # Fleet observability plane: /timeseries history + (with
            # --slo-* flags) burn-rate tracking over the Process path.
            ring, tracker = _wire_fleet_obs(
                args, metrics_server, sampler,
                latency_family="tdn_batch_wait_seconds",
                latency_match={"method": "Process"},
                availability_kwargs={
                    "total_family": "tdn_rpc_requests_total",
                    "bad_family": "tdn_rpc_errors_total",
                },
                scheduler=server.batcher,
            )
            # Flight recorder (ISSUE 11): detectors on the sampler
            # tick, bundles into --incident-dir, /debug/bundle +
            # /incidents on the endpoint.
            _wire_incident_recorder(args, metrics_server, sampler,
                                    ring, tracker)
            sampler.start()
            _attach_metrics_sampler(metrics_server, sampler)

        def teardown():
            # Drain in-flight RPCs before the engine goes away
            # (idempotent: a SIGTERM-initiated drain just gets joined).
            drain.begin()
            drain.wait(args.drain_grace_seconds + 10.0)
            engine.down()
            _stop_metrics_server(metrics_server, sampler)

        _serve_loop(engine, max_seconds=args.serve_seconds,
                    teardown=teardown, stop_event=drain.drained)
        return 0
    if args.serve:
        _serve_loop(engine, max_seconds=args.serve_seconds)
        _stop_metrics_server(metrics_server)
        return 0
    _stop_metrics_server(metrics_server)
    return 0


def cmd_infer(args) -> int:
    from tpu_dist_nn.core.schema import load_examples

    if not args.inputs:
        raise ValueError("tdn infer requires --inputs (an examples JSON file)")
    if not getattr(args, "target", None) and args.port is not None and not args.config:
        # A bare --port with no local model means "talk to the server on
        # localhost" — the reference client's default addressing
        # (run_grpc_inference.py:27: 127.0.0.1:5101).
        args.target = f"127.0.0.1:{args.port}"
    if getattr(args, "target", None):
        ignored = [
            name for name, bad in (
                ("--config", args.config is not None),
                ("--quantize", args.quantize is not None),
                ("--profile-dir", args.profile_dir is not None),
                ("--distribution", args.distribution is not None),
                ("--data-parallel", args.data_parallel != 1),
            ) if bad
        ]
        if ignored:
            raise ValueError(
                f"{', '.join(ignored)} configure a LOCAL engine and have no "
                "effect in --target client mode; start the server with them "
                "instead (tdn up --grpc-port ...)"
            )
        return _infer_over_grpc(args)
    if not args.config:
        raise ValueError("tdn infer requires --config (or --target for "
                         "client-only mode against a running server)")
    engine = _engine_from_args(args)
    x, y = load_examples(args.inputs)
    if args.input_index is not None:
        # Single-example path (run_grpc_inference.py:174-178).
        out, seconds = engine.infer_single(x[args.input_index])
        print(f"Output: {out.tolist()}")
        print(f"Inference time: {seconds:.4f} seconds")
        if y[args.input_index] >= 0:
            print(f"Label: {y[args.input_index]}  predicted: {int(out.argmax())}")
        return 0
    labels = y if (y >= 0).all() else None
    if args.profile_dir:
        from tpu_dist_nn.utils.profiling import capture_trace

        with capture_trace(args.profile_dir):
            result = engine.run_inference(x, labels=labels, batch_size=args.batch_size)
        log.info("device trace written to %s", args.profile_dir)
    else:
        result = engine.run_inference(x, labels=labels, batch_size=args.batch_size)
    for i, bs in enumerate(result.batch_seconds):
        log.info("batch %d took %.4f seconds", i, bs)
    if len(result.batch_seconds) > 1:
        log.info("batch latency: %s", json.dumps(result.latency_summary()))
    n = len(x)
    if result.metrics:
        correct = int(round(result.metrics["accuracy"] * n))
        # The client's closing report (run_grpc_inference.py:206-216).
        print(f"Correct predictions: {correct}/{n} "
              f"(accuracy {result.metrics['accuracy']:.4f})")
        print(f"Metrics: {json.dumps(result.metrics)}")
    print(f"Total inference time: {result.seconds:.4f} seconds "
          f"({n / result.seconds:.1f} samples/sec)")
    return 0


def _infer_over_grpc(args) -> int:
    """Client-only inference against a running ``tdn serve`` endpoint —
    the reference client's role (run_grpc_inference.py): no model file
    needed, batches over one persistent channel, accuracy + latency
    reported the same way."""
    import math

    import numpy as np

    from tpu_dist_nn.core.schema import load_examples
    from tpu_dist_nn.serving import GrpcClient
    from tpu_dist_nn.train.metrics import classification_metrics

    x, y = load_examples(args.inputs)
    kwargs = {}
    if getattr(args, "retry_max_attempts", None) is not None:
        # Override the client's default retry policy: 1 = single
        # attempt (the reference's behavior), N > 1 = up to N-1
        # jittered-backoff retries within the --timeout budget.
        from tpu_dist_nn.serving.resilience import RetryPolicy

        kwargs["retry"] = RetryPolicy(max_attempts=args.retry_max_attempts)
    if getattr(args, "session_key", None):
        # Rides as x-tdn-session: the router pins this client's
        # requests to one replica (an engine server ignores it).
        kwargs["session_key"] = args.session_key
    if getattr(args, "slo_class", None):
        # Rides as x-tdn-class: admission priority + shed watermark
        # (docs/ROBUSTNESS.md "Degradation ladder").
        kwargs["slo_class"] = args.slo_class
    client = GrpcClient(args.target, timeout=args.timeout or 30.0, **kwargs)
    try:
        if args.input_index is not None:
            t0 = time.monotonic()
            out = client.process(np.asarray(x[args.input_index])[None, :])[0]
            seconds = time.monotonic() - t0
            print(f"Output: {out.tolist()}")
            print(f"Inference time: {seconds:.4f} seconds")
            if y[args.input_index] >= 0:
                print(f"Label: {y[args.input_index]}  predicted: {int(out.argmax())}")
            return 0
        bs = args.batch_size or len(x)
        outs = []
        t0 = time.monotonic()
        for i in range(math.ceil(len(x) / bs)):
            tb = time.monotonic()
            outs.append(client.process(x[i * bs:(i + 1) * bs]))
            log.info("batch %d took %.4f seconds", i, time.monotonic() - tb)
        seconds = time.monotonic() - t0
        out = np.vstack(outs)
        n = len(x)
        if (y >= 0).all():
            preds = out.argmax(-1)
            metrics = classification_metrics(preds, y, out.shape[1])
            correct = int((preds == y).sum())
            print(f"Correct predictions: {correct}/{n} "
                  f"(accuracy {metrics['accuracy']:.4f})")
            print(f"Metrics: {json.dumps(metrics)}")
        print(f"Total inference time: {seconds:.4f} seconds "
              f"({n / seconds:.1f} samples/sec)")
        return 0
    finally:
        client.close()


def _parse_targets(text):
    if not text:
        return []
    return [t for t in text.replace(",", " ").split() if t]


def _parse_class_watermarks(text):
    """``--class-watermarks 'critical=1.0,best_effort=0.5'`` -> the
    validated full per-class fraction table (None = defaults). Fails
    fast on unknown classes or fractions outside [0, 1]."""
    if not text:
        return None
    from tpu_dist_nn.serving.sched_core import validate_class_watermarks

    table = {}
    for part in text.replace(",", " ").split():
        cls, sep, frac = part.partition("=")
        if not sep:
            raise ValueError(
                f"--class-watermarks entries are class=fraction, got "
                f"{part!r}"
            )
        try:
            table[cls.strip()] = float(frac)
        except ValueError:
            raise ValueError(
                f"--class-watermarks fraction for {cls.strip()!r} must "
                f"be a number, got {frac!r}"
            ) from None
    return validate_class_watermarks(table)


def cmd_router(args) -> int:
    """The multi-replica front door (docs/SCALING.md): serve the
    LayerService surface over a load-aware replica pool, or drive a
    running router's admin path (``--drain-replica`` / ``--undrain-
    replica`` / ``--list-replicas`` with ``--admin``)."""
    # ----- admin-client mode: talk to a RUNNING router's endpoint.
    admin_action = (
        ("drain", args.drain_replica) if args.drain_replica
        else ("undrain", args.undrain_replica) if args.undrain_replica
        else ("quarantine", args.quarantine_replica)
        if args.quarantine_replica
        else ("unquarantine", args.unquarantine_replica)
        if args.unquarantine_replica
        else ("replicas", None) if args.list_replicas
        else None
    )
    if admin_action is not None:
        if not args.admin:
            raise ValueError(
                "--drain-replica/--undrain-replica/--quarantine-replica/"
                "--unquarantine-replica/--list-replicas need "
                "--admin HOST:METRICS_PORT (the router's metrics "
                "endpoint, which mounts the /router/* admin routes)"
            )
        import urllib.parse

        verb, target = admin_action
        path = f"/router/{verb}"
        if target is not None:
            path += "?replica=" + urllib.parse.quote(target, safe="")
        if verb == "unquarantine" and args.force:
            path += "&force=1"
        # Drain/undrain CHANGE fleet state: POST-only on the server so
        # a GET sweep cannot actuate; the snapshot stays a GET.
        body = _endpoint_get(
            _endpoint_base(args.admin), path, args.timeout,
            method="GET" if verb == "replicas" else "POST",
        )
        print(body.decode().strip())
        return 0

    # ----- serve mode: bring up the pool + the front door.
    _apply_trace_sample_rate(args)
    _validate_slo_flags(args)
    _validate_incident_flags(args)
    _validate_autoscale_flags(args)
    _validate_hedge_flags(args)
    targets = _parse_targets(args.replicas)
    if not targets and not args.spawn:
        raise ValueError(
            "tdn router needs replicas: --replicas host:port[,host:port...] "
            "(static fleet) and/or --spawn N --config model.json "
            "(subprocess-managed local replicas)"
        )
    if args.spawn and not args.config:
        raise ValueError("--spawn needs --config (the model the local "
                         "replicas serve)")
    if len(set(targets)) != len(targets):
        # ReplicaPool.add() dedups on target, so a duplicate would
        # silently run the fleet at N-1 AND shift every later
        # --replica-metrics endpoint onto the wrong replica — the
        # same silent-misconfiguration class as the parallel-list
        # mismatch below. Fail the typo at the flag.
        dupes = sorted({t for t in targets if targets.count(t) > 1})
        raise ValueError(
            f"--replicas lists duplicate target(s): {', '.join(dupes)}"
        )
    metrics_targets = _parse_targets(args.replica_metrics)
    if metrics_targets and len(metrics_targets) != len(targets):
        # A silent mismatch would leave the tail replicas unscraped:
        # no gauge-based placement, no healthz drain choreography, and
        # invisible to --aggregate. Fail the typo at the flag.
        raise ValueError(
            f"--replica-metrics must be parallel to --replicas: got "
            f"{len(metrics_targets)} metrics endpoint(s) for "
            f"{len(targets)} replica(s)"
        )
    weights = []
    if args.replica_weights:
        try:
            weights = [float(w)
                       for w in _parse_targets(args.replica_weights)]
        except ValueError as e:
            raise ValueError(f"--replica-weights must be numbers: {e}") \
                from e
        if len(weights) != len(targets):
            # Same silent-misalignment class as --replica-metrics.
            raise ValueError(
                f"--replica-weights must be parallel to --replicas: "
                f"got {len(weights)} weight(s) for {len(targets)} "
                f"replica(s)"
            )
        if any(w <= 0 for w in weights):
            raise ValueError("--replica-weights must be > 0")
    from tpu_dist_nn.serving.pool import ReplicaPool
    from tpu_dist_nn.serving.resilience import GracefulDrain
    from tpu_dist_nn.serving.router import (
        admin_routes,
        router_health,
        serve_router,
    )

    pool = ReplicaPool(
        targets, metrics_targets, weights,
        load_staleness=args.load_staleness,
        scrape_interval=args.scrape_interval,
    )
    drain = GracefulDrain(grace_seconds=args.drain_grace_seconds)
    from tpu_dist_nn.serving.router import admin_post_routes

    metrics_server = _start_metrics_server(
        args, health_fn=drain.wrap_health(router_health(pool)),
        routes=admin_routes(pool),
        post_routes=admin_post_routes(pool),
    )
    spawned = []
    try:
        if args.spawn:
            # One engine boot (compile + warmup) can take minutes;
            # spawning sequentially would cost N x boot before the
            # router port even prints. Each spawn_local blocks only on
            # its OWN child's port lines, so boot the fleet in parallel.
            import concurrent.futures

            with concurrent.futures.ThreadPoolExecutor(
                max_workers=args.spawn, thread_name_prefix="tdn-spawn"
            ) as ex:
                futs = [
                    ex.submit(
                        pool.spawn_local, args.config,
                        extra_args=["--serve-warm-rows",
                                    str(args.spawn_warm_rows)],
                        platform=args.platform,
                    )
                    for _ in range(args.spawn)
                ]
                for fut in futs:
                    rep = fut.result()
                    spawned.append(rep)
                    print(json.dumps({
                        "replica": rep.target,
                        "metrics_target": rep.metrics_target,
                        "spawned": True,
                    }), flush=True)
        pool.start()
        hedge = None
        if args.hedge_after_p99_ratio is not None:
            from tpu_dist_nn.serving.router import HedgePolicy

            # Process-only unless --hedge-generate opted in: Generate
            # is not idempotent under sampling (docs/SCALING.md
            # "Request hedging").
            hedge = HedgePolicy(
                args.hedge_after_p99_ratio,
                methods=(("Process", "Generate") if args.hedge_generate
                         else ("Process",)),
            )
        # Integrity plane (docs/ROBUSTNESS.md "Silent corruption &
        # quarantine"): canary probes ride the scrape loop, spot-checks
        # shadow sampled Process traffic; both feed pool.quarantine.
        canary = None
        if args.canary_interval is not None:
            from tpu_dist_nn.serving.integrity import CanaryProber

            dim = args.canary_dim
            if dim is None and args.config:
                from tpu_dist_nn.core.schema import load_model

                dim = load_model(args.config).input_dim
            if dim is None:
                raise ValueError(
                    "--canary-interval needs the canary input width: "
                    "pass --canary-dim D, or --config MODEL.json to "
                    "derive it from the model"
                )
            canary = CanaryProber(dim=dim,
                                  interval=args.canary_interval)
        spotcheck = None
        if args.spotcheck_rate:
            from tpu_dist_nn.serving.integrity import SpotChecker

            spotcheck = SpotChecker(
                pool, rate=args.spotcheck_rate, canary=canary,
                on_verdict=lambda target, reason, ev: pool.quarantine(
                    target, reason=reason, evidence=ev
                ),
            )
        server, bound = serve_router(pool, args.port, hedge=hedge,
                                     canary=canary, spotcheck=spotcheck)
        drain.add_server(server)
        drain.install_signal_handler()
        print(json.dumps({
            "router_port": bound,
            "replicas": pool.targets(),
            "hedging": sorted(hedge.methods) if hedge else None,
            "canary_interval": args.canary_interval,
            "spotcheck_rate": args.spotcheck_rate or None,
        }), flush=True)
        sampler = None
        if metrics_server is not None:
            from tpu_dist_nn.obs import RuntimeSampler, TRACER

            sampler = RuntimeSampler()
            sampler.add_pool(pool)
            sampler.add_tracer(TRACER)
            # Fleet observability plane: the router's own latency SLO
            # rides tdn_router_request_seconds; availability counts
            # every non-ok outcome against the budget.
            ring, tracker = _wire_fleet_obs(
                args, metrics_server, sampler,
                latency_family="tdn_router_request_seconds",
                availability_kwargs={
                    "total_family": "tdn_router_requests_total",
                    "bad_exclude": {"outcome": "ok"},
                },
            )
            # Fleet autopilot (ISSUE 12): the control loop ticks on
            # the SAME sampler cadence, after the SLO tracker it reads
            # burn from; scale-up spawns local replicas through the
            # pool (needs --config), scale-down runs the observed-
            # drain choreography. POST /router/scale is the manual
            # override either way.
            autoscaler = None
            if args.autoscale_min is not None:
                from tpu_dist_nn.serving.autoscale import Autoscaler

                spawner = None
                if args.config:
                    spawner = lambda: pool.spawn_local(  # noqa: E731
                        args.config,
                        extra_args=["--serve-warm-rows",
                                    str(args.spawn_warm_rows)],
                        platform=args.platform,
                    )
                autoscaler = Autoscaler(
                    pool,
                    min_replicas=args.autoscale_min,
                    max_replicas=args.autoscale_max,
                    target_occupancy=args.autoscale_target_occupancy,
                    spawner=spawner, slo=tracker,
                )
                sampler.add_autoscaler(autoscaler)
                print(json.dumps({
                    "autoscale_min": args.autoscale_min,
                    "autoscale_max": args.autoscale_max,
                    "autoscale_target_occupancy":
                        args.autoscale_target_occupancy,
                    "autoscale_spawner": bool(spawner),
                }), flush=True)
            metrics_server.add_post_routes(
                admin_post_routes(pool, autoscaler)
            )
            metrics_server.add_routes(
                admin_routes(pool, autoscaler=autoscaler)
            )
            # Flight recorder, fleet flavor: on trigger the router
            # fans /debug/bundle out to every replica within the tick
            # and stitches the fleet trace into ONE incident.
            recorder = _wire_incident_recorder(args, metrics_server,
                                               sampler, ring, tracker,
                                               pool=pool, router=True)
            if recorder is not None:
                # Quarantine freezes its evidence IMMEDIATELY (not on
                # the next detector tick): the bundle names the
                # detector verdict — fingerprint mismatch, off-golden
                # canary digest, spot-check disagreement — while the
                # fleet state that produced it is still current.
                def _quarantine_bundle(target, reason, evidence,
                                       _rec=recorder):
                    _rec.capture(
                        f"quarantine_{reason}",
                        reason=f"replica {target} quarantined "
                               f"({reason})",
                        details={"replica": target, "reason": reason,
                                 "evidence": evidence},
                    )

                pool.on_quarantine = _quarantine_bundle
            sampler.start()
            _attach_metrics_sampler(metrics_server, sampler)
        try:
            if args.serve_seconds is not None:
                drain.wait(args.serve_seconds)
            else:
                server.wait_for_termination()
        except KeyboardInterrupt:
            log.info("interrupt received; draining router")
        drain.begin()
        drain.wait(args.drain_grace_seconds + 10.0)
        _stop_metrics_server(metrics_server, sampler)
        return 0
    finally:
        # close() owns spawned-child teardown (SIGTERM -> their own
        # GracefulDrain -> hard kill past the grace budget).
        pool.close(grace=args.drain_grace_seconds + 10.0)


def cmd_fleet(args) -> int:
    """``tdn fleet manifest``: emit docker-compose or k8s specs for a
    replica fleet + router, sized from ``--replicas-count`` or from a
    RUNNING router's ``/router/replicas`` snapshot (``--admin``) — so
    remote fleets inherit the same drain/rejoin automation ``--spawn``
    fleets get locally (docs/SCALING.md "Fleet manifests")."""
    from tpu_dist_nn.serving.manifest import (
        build_spec,
        compose_manifest,
        k8s_manifest,
        spec_from_snapshot,
    )

    autoscale = None
    if args.autoscale_min is not None or args.autoscale_max is not None:
        if args.autoscale_min is None or args.autoscale_max is None:
            raise ValueError(
                "--autoscale-min and --autoscale-max must be passed "
                "together"
            )
        autoscale = {
            "min": args.autoscale_min, "max": args.autoscale_max,
            "target_occupancy": args.autoscale_target_occupancy,
        }
    kwargs = dict(
        config=args.config, image=args.image,
        grpc_base_port=args.grpc_base_port,
        metrics_base_port=args.metrics_base_port,
        router_port=args.router_port,
        router_metrics_port=args.router_metrics_port,
        drain_grace_seconds=args.drain_grace_seconds,
        warm_rows=args.spawn_warm_rows,
        autoscale=autoscale,
        hedge_after_p99_ratio=args.hedge_after_p99_ratio,
    )
    if args.admin:
        body = _endpoint_get(
            _endpoint_base(args.admin), "/router/replicas", args.timeout
        )
        spec = spec_from_snapshot(json.loads(body), **kwargs)
    else:
        if args.replicas_count is None:
            raise ValueError(
                "tdn fleet manifest needs --replicas-count N, or "
                "--admin HOST:METRICS_PORT to size the manifest from "
                "a running router's fleet"
            )
        spec = build_spec(args.replicas_count, **kwargs)
    text = (compose_manifest(spec) if args.format == "compose"
            else k8s_manifest(spec))
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(json.dumps({"wrote": args.out, "format": args.format,
                          "replicas": spec["replicas"]}))
    else:
        print(text, end="")
    return 0


def cmd_train(args) -> int:
    _apply_trace_sample_rate(args)
    _validate_checkpoint_flags(args)
    _validate_metrics_out(args)
    from tpu_dist_nn.core.schema import load_model
    from tpu_dist_nn.data.datasets import (
        load_mnist_idx,
        synthetic_fashion_mnist,
        synthetic_mnist,
    )
    from tpu_dist_nn.models.fcnn import init_fcnn, spec_from_params
    from tpu_dist_nn.train.trainer import TrainConfig
    import jax

    if args.config:
        model = load_model(args.config)
    else:
        if args.layers is None:
            # Dataset-aware default (an explicit --layers always wins —
            # the argparse default is None, so it cannot be confused
            # with a deliberately passed value): the reference's
            # 784-128-64-10 torch shape, or its geometry at the 8x8
            # vendored-digits size.
            args.layers = "64,32,16,10" if args.data == "digits" else "784,128,64,10"
            log.info("using default layers %s", args.layers)
        sizes = _parse_distribution(args.layers)
        acts = ["relu"] * (len(sizes) - 2) + ["softmax"]
        params = init_fcnn(jax.random.key(args.seed), sizes, acts)
        model = spec_from_params(params, acts)

    if args.data.startswith("idx:"):
        data = load_mnist_idx(args.data[4:], "train")
        eval_data = load_mnist_idx(args.data[4:], "test")
    elif args.data == "digits":
        # Vendored REAL handwritten digits (datasets.real_digits):
        # held-out accuracy here is a genuine generalization number.
        from tpu_dist_nn.data.datasets import real_digits

        data = real_digits("train")
        eval_data = real_digits("test")
    elif args.data.startswith("json:"):
        from tpu_dist_nn.core.schema import load_examples
        from tpu_dist_nn.data.datasets import Dataset

        x, y = load_examples(args.data[5:])
        if (y < 0).any():
            # load_examples marks missing labels with -1 (fine for pure
            # inference, cmd_infer guards on it) — training on the
            # sentinel would silently push everything to the last class.
            raise ValueError(
                f"{args.data[5:]}: examples without labels cannot be trained on"
            )
        full = Dataset(x, y, int(y.max()) + 1)
        data, eval_data = full.split(0.9, seed=args.seed)
    else:  # synthetic | fashion
        make = synthetic_fashion_mnist if args.data == "fashion" else synthetic_mnist
        full = make(
            args.num_examples, dim=model.input_dim,
            num_classes=model.output_dim, seed=args.seed,
        )
        data, eval_data = full.split(0.9, seed=args.seed)
    if data.x.shape[1] != model.input_dim:
        from tpu_dist_nn.utils.errors import InvalidArgumentError

        raise InvalidArgumentError(
            f"data has {data.x.shape[1]} features but the model expects "
            f"{model.input_dim} inputs — pass --layers (or --config) "
            f"matching the dataset (e.g. --data digits is 64-dim)"
        )

    from tpu_dist_nn.api.engine import Engine

    engine = Engine.up(
        model,
        _parse_distribution(args.distribution),
        data_parallel=args.data_parallel,
        num_microbatches=args.microbatches,
        virtual_stages=args.virtual_stages,
    )

    import jax as _jax

    from tpu_dist_nn.data.datasets import Dataset
    from tpu_dist_nn.data.feed import shard_for_host

    if _jax.process_count() > 1:
        if engine.pipelined and engine._hp is None:
            # Multi-host data parallelism: each process trains on its
            # stripe; the pipelined trainer assembles the stripes into
            # one globally-sharded batch per step (eval stays global so
            # every host reports the same metrics).
            sx, sy = shard_for_host(data.x, data.y)
            data = Dataset(sx, sy, data.num_classes)
        else:
            # No global-mesh trainer for this placement: striping would
            # silently train N divergent models. Train replicated on the
            # full (identical) dataset instead — correct, just without
            # cross-host speedup.
            log.warning(
                "multi-host job with a non-pipelined placement: training "
                "replicated per host on the full dataset (use a "
                "multi-stage --distribution for cross-host parallelism)"
            )
    cfg = TrainConfig(
        learning_rate=args.lr, epochs=args.epochs,
        batch_size=args.batch_size, seed=args.seed,
        clip_norm=args.clip_norm, warmup_steps=args.warmup_steps,
        lr_schedule=args.lr_schedule, weight_decay=args.weight_decay,
        grad_accum=args.grad_accum,
    )
    checkpoints = None
    if args.checkpoint_dir:
        checkpoints = _make_checkpoint_manager(args)
    # Scrapers watch the run live (step/loss/checkpoint families from
    # the trainer); /healthz mirrors the engine while it trains
    # (probe=False: no device dispatch from the HTTP thread mid-step).
    metrics_server = _start_metrics_server(
        args, health_fn=lambda: engine.health(probe=False)
    )
    try:
        history = engine.train(
            data, cfg, eval_data=eval_data, checkpoints=checkpoints,
            schedule=args.schedule,
        )
    finally:
        _stop_metrics_server(metrics_server)
    if args.metrics_out:
        _write_metrics_jsonl(args.metrics_out, history)
    for h in history:
        msg = f"epoch {h['epoch']}: loss {h['loss']:.4f} ({h['seconds']:.2f}s)"
        if "eval" in h:
            msg += f" eval_acc {h['eval']['accuracy']:.4f}"
        log.info(msg)
    metrics = history[-1].get("eval") if history else None
    if args.out:
        engine.export(args.out, metrics=metrics)
        log.info("exported trained model to %s", args.out)
    return 0


def _default_virtual(args, sched: str) -> int:
    """--virtual-stages defaulting shared by every pipelined-LM branch:
    interleaved is pointless at v=1 (it IS the v>1 placement), zb's
    documented default is the classic contiguous v=1 placement, and
    zb-v's placement fixes v=2."""
    if sched == "zb-v":
        return 2
    v = getattr(args, "virtual_stages", None)
    if v is None:
        v = 2 if sched == "interleaved" else 1
    return v


def _lm_block_layout(sched: str, stages: int, num_virtual: int, *,
                     cfg=None, tp: int = 1, ep: int = 0):
    """Thin alias for
    :func:`tpu_dist_nn.train.lm_trainer.lm_block_layout` (the shared
    (schedule, sharding) -> layout dispatch lives with the trainers so
    examples and tests can reuse it without importing the CLI)."""
    from tpu_dist_nn.train.lm_trainer import lm_block_layout

    return lm_block_layout(sched, stages, num_virtual, cfg=cfg, tp=tp, ep=ep)


def _lm_stream_demo(args) -> int:
    """Client-only streaming demo — ``tdn infer --target``'s role for
    the streaming plane (``tdn lm --stream --target HOST:PORT``): no
    training, no model file — connect to a running ``--serve-generate``
    endpoint (or a router in front of a fleet), stream ONE generation
    of ``--prompt`` over ``LayerService/GenerateStream``, print bytes
    as each token frame LANDS (first output at ~TTFT, not retirement),
    then a JSON latency summary (TTFT + inter-token gaps + terminal)."""
    import sys

    import numpy as np

    from tpu_dist_nn.data.text import decode as decode_text
    from tpu_dist_nn.data.text import encode
    from tpu_dist_nn.serving import GrpcClient

    if not getattr(args, "target", None):
        raise ValueError(
            "tdn lm --stream is client-only: pass --target HOST:PORT of "
            "a running --serve-generate endpoint (continuous scheduler; "
            "a router front door works too)"
        )
    T = args.serve_prompt_len
    ids = encode(args.prompt).tolist()
    # The endpoint decodes ONE static prompt shape; pad on the LEFT
    # (byte 32, space) so the real text stays adjacent to the
    # generated continuation, and keep the tail when too long.
    row = ([32] * max(0, T - len(ids)) + ids)[-T:]
    client = GrpcClient(args.target, session_key=getattr(
        args, "session_key", None))
    t0 = time.monotonic()
    first = None
    last = None
    gaps: list[float] = []
    n = 0
    try:
        reply = client.generate_stream(np.asarray([row], np.int64))
        for tok in reply:
            now = time.monotonic()
            if first is None:
                first = now - t0
            else:
                gaps.append(now - last)
            last = now
            n += 1
            sys.stdout.write(decode_text([tok]))
            sys.stdout.flush()
        sys.stdout.write("\n")
        summary = {
            "tokens": n,
            "ttft_s": round(first, 6) if first is not None else None,
            "intertoken_p50_ms": (
                round(sorted(gaps)[len(gaps) // 2] * 1000, 3)
                if gaps else None
            ),
            "intertoken_max_ms": (
                round(max(gaps) * 1000, 3) if gaps else None
            ),
            "finish": reply.finish,
            "trace_id": reply.trace_id,
        }
        print(json.dumps(summary), flush=True)
        return 0
    finally:
        client.close()


def _lm_serve_model_config(args) -> int:
    """``tdn lm --model-config FILE --serve-generate PORT``: no training;
    the architecture the file describes, with seeded random weights,
    through ``serve_lm_generate`` and the continuous scheduler."""
    import jax

    from tpu_dist_nn.models.sala import init_model_config, load_model_config
    from tpu_dist_nn.serving.resilience import GracefulDrain

    if getattr(args, "serve_generate", None) is None:
        raise ValueError(
            "--model-config serves a model; give --serve-generate PORT")
    _apply_trace_sample_rate(args)
    _validate_slo_flags(args, needs="serve-generate")
    _validate_incident_flags(args, needs="serve-generate")
    cfg = load_model_config(args.model_config)
    params, count, kinds = init_model_config(jax.random.key(args.seed), cfg)
    report = {"model_config": args.model_config,
              "params": count, "param_dtype": cfg.param_dtype,
              "layers": list(kinds)}
    drain = GracefulDrain(grace_seconds=args.drain_grace_seconds)
    metrics_server = _start_metrics_server(
        args, health_fn=drain.wrap_health(None)
    )
    return _serve_generate_and_wait(
        args, params, cfg, report, drain, metrics_server
    )


def _serve_generate_and_wait(args, params, cfg, report: dict, drain,
                             metrics_server) -> int:
    """Serve generation from ``params`` behind ``--serve-generate`` and
    wait for termination: the tail of ``tdn lm``, shared by the trained
    LM and a ``--model-config`` model."""
    # Serve GENERATION from the just-trained params (VERDICT r4
    # item 7: the continuous-batching decoder behind the serving
    # layer). The port is printed in the JSON line BEFORE blocking
    # so drivers/tests can connect.
    from tpu_dist_nn.serving import serve_lm_generate

    # (Flag combination fully validated pre-training, top of cmd_lm.)
    server, bound = serve_lm_generate(
        params, cfg, args.serve_generate,
        max_new_tokens=args.serve_new_tokens,
        prompt_len=args.serve_prompt_len,
        num_stages=args.serve_stages,
        num_groups=args.serve_groups,
        temperature=args.temperature, top_k=args.top_k,
        top_p=args.top_p, seed=args.seed,
        max_pending_rows=args.max_pending_rows,
        class_watermarks=_parse_class_watermarks(
            getattr(args, "class_watermarks", None)
        ),
        scheduler=args.scheduler, gen_slots=args.gen_slots,
        eos_id=args.eos_id,
        prefix_cache_blocks=args.prefix_cache_blocks,
        prefill_chunk=args.prefill_chunk,
        # Continuous mode: open the port hot (warm compiles exactly
        # the prefill-at-slot + step kernels). The static arm keeps
        # its cold default — its bucket ladder warm is opt-in.
        warm_rows=(
            1 if args.scheduler == "continuous"
            or (args.scheduler == "auto" and args.serve_stages == 1)
            else 0
        ),
    )
    # SIGTERM → graceful drain (healthz NOT_SERVING, stop
    # accepting, finish in-flight) instead of hard-killing decodes.
    drain.add_server(server)
    drain.install_signal_handler()
    report["serving"] = {
        "port": bound,
        "prompt_len": args.serve_prompt_len,
        "max_new_tokens": args.serve_new_tokens,
        "stages": args.serve_stages,
        "scheduler": (
            "continuous" if server.scheduler is not None else "static"
        ),
    }
    if server.scheduler is not None:
        report["serving"]["gen_slots"] = args.gen_slots
        report["serving"]["prefix_cache_blocks"] = \
            args.prefix_cache_blocks
        report["serving"]["prefill_chunk"] = args.prefill_chunk
    sampler = None
    if metrics_server is not None and server.batcher is not None:
        from tpu_dist_nn.obs import RuntimeSampler, TRACER

        sampler = RuntimeSampler()
        sampler.add_batcher(server.batcher, method="Generate")
        if server.scheduler is not None:
            sampler.add_generation_scheduler(server.scheduler)
        sampler.add_tracer(TRACER)
        # Fleet observability plane for the generation endpoint:
        # the latency SLO covers submit -> retirement (the wire
        # figure a client sees), availability the Generate aborts.
        ring, tracker = _wire_fleet_obs(
            args, metrics_server, sampler,
            latency_family="tdn_batch_wait_seconds",
            latency_match={"method": "Generate"},
            availability_kwargs={
                "total_family": "tdn_rpc_requests_total",
                "bad_family": "tdn_rpc_errors_total",
            },
            scheduler=server.batcher,
        )
        # Flight recorder over the generation endpoint: a burn,
        # shed storm, or crash mid-decode leaves its bundle.
        _wire_incident_recorder(args, metrics_server, sampler,
                                ring, tracker)
        sampler.start()
        _attach_metrics_sampler(metrics_server, sampler)
    print(json.dumps(report), flush=True)
    try:
        if args.serve_seconds is not None:
            # A SIGTERM-initiated drain ends the wait early.
            drain.wait(args.serve_seconds)
        else:
            server.wait_for_termination()
    except KeyboardInterrupt:
        pass
    drain.begin()
    drain.wait(args.drain_grace_seconds + 10.0)
    _stop_metrics_server(metrics_server, sampler)
    return 0


def cmd_lm(args) -> int:
    """Train + evaluate the Tiny-Transformer LM (BASELINE configs[4]).

    Corpus tiers (data/text.py load_corpus): a real on-disk WikiText
    file when present (``--corpus`` or the conventional paths), else
    the VENDORED real corpus shipped with the package (~238 KB of real
    English from the Debian common-licenses texts — the default on
    this zero-egress box), else the synthetic gated fallback.
    Pipelined over ``--stages`` when > 1.
    """
    if getattr(args, "stream", False):
        # Client-only streaming demo: nothing below (training, model
        # construction) applies — bail before the heavy imports.
        return _lm_stream_demo(args)
    if getattr(args, "model_config", None):
        return _lm_serve_model_config(args)
    import jax

    from tpu_dist_nn.data.text import lm_sequences, load_corpus, encode
    from tpu_dist_nn.data.text import lm_batches
    from tpu_dist_nn.models.transformer import (
        TransformerConfig,
        init_transformer,
        num_params,
    )
    from tpu_dist_nn.train.lm_trainer import (
        LMTrainConfig,
        evaluate_lm,
        train_lm,
    )

    _apply_trace_sample_rate(args)
    _validate_slo_flags(args, needs="serve-generate")
    _validate_incident_flags(args, needs="serve-generate")
    moe = args.experts > 0
    # (MoE x --seq-parallel is rejected below with the other
    # seq-parallel compatibility checks, with or without --stages.)
    if not moe and args.expert_parallel > 1:
        raise ValueError("--expert-parallel requires --experts > 0")
    if args.schedule == "zb-v" and getattr(args, "virtual_stages", None) not in (
        None, 2,
    ):
        raise ValueError(
            "--schedule zb-v fixes the chunk count at 2 per device (the "
            "V placement's two legs); drop --virtual-stages or use "
            "--schedule zb for a free chunk count"
        )
    if args.tensor_parallel > 1:
        if moe:
            # TP-INSIDE-EXPERTS (round 5; previously rejected as
            # "expert banks are already sharded"): each expert's FFN
            # Megatron-splits over `model` on the flat mesh. The
            # pipelined product stays out of scope (README footnote).
            if args.stages > 1:
                raise ValueError(
                    "--tensor-parallel x --experts x --stages is out "
                    "of scope: TP-inside-experts runs on the flat "
                    "(model, expert, data) mesh; pipelined MoE shards "
                    "experts over `expert` (README matrix footnote)"
                )
            if args.seq_parallel > 1:
                raise ValueError(
                    "--tensor-parallel x --experts x --seq-parallel "
                    "is out of scope (README matrix footnote)"
                )
            if (4 * args.d_model) % args.tensor_parallel:
                raise ValueError(
                    f"d_ff={4 * args.d_model} must be divisible by "
                    f"--tensor-parallel {args.tensor_parallel} "
                    "(TP-inside-experts shards the FF dim)"
                )
        else:
            if args.stages <= 1:
                raise ValueError(
                    "--tensor-parallel shards each pipeline stage's "
                    "blocks: it requires --stages > 1 (use "
                    "--sample-tensor-parallel for sharded decode)"
                )
            if args.heads % args.tensor_parallel:
                raise ValueError(
                    f"--heads {args.heads} must be divisible by "
                    f"--tensor-parallel {args.tensor_parallel} "
                    "(Megatron shards attention head-wise)"
                )
    if args.sample_tensor_parallel > 1 and args.sample_bytes <= 0:
        raise ValueError(
            "--sample-tensor-parallel requires --sample-bytes > 0 "
            "(it shards the decode; without sampling it would be "
            "silently ignored)"
        )
    if args.sample_pipeline_stages > 1 and args.sample_bytes <= 0:
        raise ValueError(
            "--sample-pipeline-stages requires --sample-bytes > 0 "
            "(it places the decode; without sampling it would be "
            "silently ignored)"
        )
    if getattr(args, "eos_id", None) is not None and not (
        0 <= args.eos_id < 256
    ):
        # Byte-level vocab: the shared validator would reject this too,
        # but only after training — fail the flag before the run.
        raise ValueError(
            f"--eos-id must be a byte id in [0, 256), got {args.eos_id}"
        )
    if getattr(args, "gen_slots", 8) < 1:
        raise ValueError(f"--gen-slots must be >= 1, got {args.gen_slots}")
    if getattr(args, "prefill_chunk", None) is not None \
            and args.prefill_chunk < 1:
        raise ValueError(
            f"--prefill-chunk must be >= 1, got {args.prefill_chunk}"
        )
    if getattr(args, "prefix_cache_blocks", 0) < 0:
        raise ValueError(
            f"--prefix-cache-blocks must be >= 0, got "
            f"{args.prefix_cache_blocks}"
        )
    if getattr(args, "serve_generate", None) is not None:
        # Validate the WHOLE serving request BEFORE training — every
        # constraint serve_lm_generate would raise after, so a bad flag
        # combination cannot discard a long run.
        if moe:
            raise ValueError("--serve-generate supports the dense LM only")
        if args.scheduler == "continuous" and args.serve_stages > 1:
            raise ValueError(
                "--scheduler continuous is single-chip; --serve-stages "
                "> 1 serves the pipelined overlapped decoder (use "
                "--scheduler static or auto)"
            )
        if args.eos_id is not None and args.serve_stages > 1:
            raise ValueError(
                "--eos-id is not supported by the pipelined overlapped "
                "decoder; serve --serve-stages 1 for stop-token "
                "semantics"
            )
        if (args.prefix_cache_blocks or args.prefill_chunk is not None) \
                and (args.scheduler == "static" or args.serve_stages > 1):
            raise ValueError(
                "--prefix-cache-blocks / --prefill-chunk are continuous-"
                "scheduler features; drop --scheduler static / "
                "--serve-stages > 1 (or drop the prefix/chunk flags)"
            )
        if (args.prefix_cache_blocks
                and args.prefill_chunk is not None
                and args.prefill_chunk > args.serve_prompt_len - 1):
            raise ValueError(
                f"--prefix-cache-blocks needs a cacheable tier: "
                f"--prefill-chunk {args.prefill_chunk} must be <= "
                f"--serve-prompt-len - 1 = {args.serve_prompt_len - 1}"
            )
        if args.layers % max(args.serve_stages, 1):
            raise ValueError(
                f"--layers {args.layers} must be divisible by "
                f"--serve-stages {args.serve_stages}"
            )
        if args.serve_prompt_len + args.serve_new_tokens - 1 > args.seq_len:
            # total-1 positions are embedded (the final sampled token
            # is returned, never fed back) — the shared validator's
            # boundary (models/generate.validate_generate_args).
            raise ValueError(
                f"--serve-prompt-len {args.serve_prompt_len} + "
                f"--serve-new-tokens {args.serve_new_tokens} - 1 must fit "
                f"--seq-len {args.seq_len} (the positional table)"
            )
        if (args.serve_groups is not None
                and args.serve_groups < args.serve_stages):
            raise ValueError(
                f"--serve-groups {args.serve_groups} must be >= "
                f"--serve-stages {args.serve_stages} (the round-robin "
                "grants each group G ticks before its next decode)"
            )
        if args.serve_stages > 1:
            import jax as _jax_sg

            n_dev = len(_jax_sg.devices())
            if n_dev < args.serve_stages:
                raise ValueError(
                    f"--serve-stages {args.serve_stages} needs "
                    f"{args.serve_stages} devices; {n_dev} available"
                )
    if args.sample_bytes > 0:
        # Validate the whole sampling request BEFORE training so a bad
        # flag combination can't discard a long run.
        if moe:
            raise ValueError("--sample-bytes supports the dense LM only")
        if args.temperature < 0:
            raise ValueError("--temperature must be >= 0")
        prompt_len = len(encode(args.prompt))
        if prompt_len == 0:
            raise ValueError("--prompt must be non-empty")
        if prompt_len >= args.seq_len:
            raise ValueError(
                f"--prompt is {prompt_len} bytes but must be shorter than "
                f"--seq-len {args.seq_len} to leave room for generation"
            )
        if args.sample_bytes > args.seq_len - prompt_len:
            raise ValueError(
                f"--sample-bytes {args.sample_bytes} does not fit: the "
                f"{prompt_len}-byte prompt leaves {args.seq_len - prompt_len} "
                f"positions within --seq-len {args.seq_len}"
            )
        if args.eos_id is not None and (
            args.sample_pipeline_stages > 1
            or args.sample_tensor_parallel > 1
        ):
            raise ValueError(
                "--eos-id applies to the single-chip decode only (the "
                "pipelined/tensor-parallel decoders have no done-mask); "
                "drop the placement flag to sample with a stop token"
            )
        spp = args.sample_pipeline_stages
        if spp > 1:
            if args.sample_tensor_parallel > 1:
                raise ValueError(
                    "--sample-pipeline-stages and --sample-tensor-parallel "
                    "are different decode placements: pick one"
                )
            if _jax_process_count() > 1:
                raise ValueError(
                    "--sample-pipeline-stages is single-host only"
                )
            if spp > len(jax.devices()):
                raise ValueError(
                    f"--sample-pipeline-stages {spp} needs {spp} devices; "
                    f"{len(jax.devices())} available"
                )
            if args.layers % spp:
                raise ValueError(
                    f"--sample-pipeline-stages {spp} must divide "
                    f"--layers ({args.layers})"
                )
        stp = args.sample_tensor_parallel
        if stp > 1:
            if _jax_process_count() > 1:
                raise ValueError(
                    "--sample-tensor-parallel is single-host only: its "
                    "decode mesh takes the first N devices, which live on "
                    "process 0 in a multi-host job; drop the flag (the "
                    "single-chip decode runs replicated per host)"
                )
            if stp > len(jax.devices()):
                raise ValueError(
                    f"--sample-tensor-parallel {stp} needs {stp} devices; "
                    f"{len(jax.devices())} available"
                )
            if args.heads % stp or (4 * args.d_model) % stp:
                raise ValueError(
                    f"--sample-tensor-parallel {stp} must divide --heads "
                    f"({args.heads}) and d_ff (4*--d-model = {4 * args.d_model})"
                )

    _validate_checkpoint_flags(args)
    _validate_metrics_out(args)
    # (--remat composes with MoE since round 4: every MoE scan body
    # wraps moe_block_apply in maybe_remat.)
    if args.zero1 and moe:
        raise ValueError("--zero1 supports the dense LM only")
    if (args.seq_parallel > 1 and moe and args.stages > 1
            and args.schedule != "gpipe"):
        raise ValueError(
            "--experts x --seq-parallel x --stages supports --schedule "
            "gpipe only (three-axis MoE rides the branch-free gpipe "
            "executor; the scheduled executors' three-axis product is "
            "out of scope — README matrix footnote)"
        )
    if args.fsdp and moe:
        raise ValueError("--fsdp supports the dense LM only")
    common = dict(
        vocab_size=256,  # byte-level
        d_model=args.d_model,
        n_heads=args.heads,
        n_layers=args.layers,
        d_ff=4 * args.d_model,
        # The sp loss feeds full (seq_len+1)-token rows (inputs +
        # next-token targets) through the forward, so its positional
        # table needs one extra row.
        max_seq_len=args.seq_len + (1 if args.seq_parallel > 1 else 0),
        compute_dtype="bfloat16" if args.bf16 else "float32",
        remat=args.remat,
    )
    mesh = None
    step_fn = None
    unshard_fn = None
    shard_fn = None  # applied to freshly-init params before training
    schedule_handled = False  # a step_fn branch that consumes --schedule
    global_mesh = None  # the mesh cross-host batches assemble over, if any
    global_span = 1     # how many ways that mesh shards the batch axis
    global_axes = "_data_"
    if moe:
        # One dispatch site for the whole MoE family: config, init,
        # train-step factory, eval, and the EP shard/unshard pair.
        from tpu_dist_nn.parallel.expert_parallel import (
            MoEConfig,
            ep_shard_blocks,
            ep_unshard_blocks,
            init_moe_transformer,
        )
        from tpu_dist_nn.train.lm_trainer import (
            evaluate_moe_lm,
            make_moe_lm_train_step,
        )

        cfg = MoEConfig(
            **common, n_experts=args.experts,
            capacity_factor=args.capacity_factor,
            router_top_k=args.router_top_k,
        )
        init_fn, eval_fn = init_moe_transformer, evaluate_moe_lm
        ep, dp = args.expert_parallel, args.data_parallel
        if args.stages > 1 and args.seq_parallel > 1:
            # THREE-AXIS MoE (round 5; previously rejected): pipeline x
            # sequence x expert parallelism on the (stage, seq, expert,
            # data) mesh — gpipe only (validated above), full rows with
            # the sp masking convention.
            from tpu_dist_nn.parallel.expert_parallel import (
                shard_blocks_pp_ep,
                unshard_blocks_pp_ep,
            )
            from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
            from tpu_dist_nn.train.lm_trainer import (
                make_pipeline_moe_lm_train_step,
            )

            if args.layers % args.stages:
                raise ValueError(
                    f"--layers {args.layers} must be divisible by "
                    f"--stages {args.stages}"
                )
            if (args.seq_len + 1) % args.seq_parallel:
                raise ValueError(
                    f"--seq-len+1 ({args.seq_len + 1}) must be divisible "
                    f"by --seq-parallel {args.seq_parallel} (rows carry "
                    "the next-token target)"
                )
            if args.batch_size % (args.microbatches * max(ep, 1) * dp):
                raise ValueError(
                    f"--batch-size {args.batch_size} must be divisible by "
                    f"microbatches*expert_parallel*data_parallel="
                    f"{args.microbatches * max(ep, 1) * dp}"
                )
            pp_sp_ep_mesh = build_mesh(MeshSpec(
                stage=args.stages, seq=args.seq_parallel,
                expert=max(ep, 1), data=dp,
            ))
            global_mesh, global_span = pp_sp_ep_mesh, max(ep, 1) * dp
            global_axes = "_data_expert_"
            schedule_handled = True
            _stages, _mb = args.stages, args.microbatches
            _mode, _ep = args.sp_mode, max(ep, 1)
            step_fn = lambda opt: make_pipeline_moe_lm_train_step(  # noqa: E731
                pp_sp_ep_mesh, cfg, _stages, _mb, opt, schedule="gpipe",
                sp_mode=_mode,
            )
            shard_fn = lambda p: dict(  # noqa: E731
                p, blocks=shard_blocks_pp_ep(p["blocks"], _stages, _ep)
            )
            unshard_fn = lambda p: dict(  # noqa: E731
                p, blocks=unshard_blocks_pp_ep(p["blocks"])
            )
        elif args.stages > 1:
            # Pipeline x expert parallelism: MoE blocks pipelined over
            # `stage`, experts sharded over `expert` inside each stage,
            # batch over (data, expert) — round 4, previously rejected.
            from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
            from tpu_dist_nn.train.lm_trainer import (
                make_pipeline_moe_lm_train_step,
            )

            if args.layers % args.stages:
                raise ValueError(
                    f"--layers {args.layers} must be divisible by "
                    f"--stages {args.stages}"
                )
            if args.batch_size % (args.microbatches * max(ep, 1) * dp):
                raise ValueError(
                    f"--batch-size {args.batch_size} must be divisible by "
                    f"microbatches*expert_parallel*data_parallel="
                    f"{args.microbatches * max(ep, 1) * dp}"
                )
            pp_ep_mesh = build_mesh(MeshSpec(
                stage=args.stages, expert=max(ep, 1), data=dp
            ))
            global_mesh, global_span = pp_ep_mesh, max(ep, 1) * dp
            global_axes = "_data_expert_"
            schedule_handled = True  # MoE x pp consumes --schedule itself
            _stages, _mb, _sched = args.stages, args.microbatches, args.schedule
            _ep = max(ep, 1)
            _v = _default_virtual(args, _sched)
            step_fn = lambda opt: make_pipeline_moe_lm_train_step(  # noqa: E731
                pp_ep_mesh, cfg, _stages, _mb, opt, schedule=_sched,
                num_virtual=_v,
            )
            _shard_b, _unshard_b = _lm_block_layout(
                _sched, _stages, _v, ep=_ep
            )
            shard_fn = lambda p: dict(p, blocks=_shard_b(p["blocks"]))  # noqa: E731
            unshard_fn = lambda p: dict(p, blocks=_unshard_b(p["blocks"]))  # noqa: E731
        elif args.seq_parallel > 1:
            # Long-context MoE (round 4, previously "dense LM only"):
            # sequence parallelism x expert parallelism on the flat
            # (seq, expert, data) mesh — ring/Ulysses attention over
            # `seq`, all_to_all dispatch over `expert`, full
            # (input+target) rows with the sp masking convention.
            from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
            from tpu_dist_nn.train.lm_trainer import (
                make_sp_moe_lm_train_step,
            )

            if (args.seq_len + 1) % args.seq_parallel:
                raise ValueError(
                    f"--seq-len+1 ({args.seq_len + 1}) must be divisible "
                    f"by --seq-parallel {args.seq_parallel} (rows carry "
                    "the next-token target)"
                )
            if args.batch_size % (max(ep, 1) * dp):
                raise ValueError(
                    f"--batch-size {args.batch_size} must be divisible "
                    f"by expert_parallel*data_parallel={max(ep, 1) * dp}"
                )
            sp_ep_mesh = build_mesh(MeshSpec(
                seq=args.seq_parallel, expert=max(ep, 1), data=dp
            ))
            global_mesh, global_span = sp_ep_mesh, max(ep, 1) * dp
            global_axes = "_data_expert_"
            _mode = args.sp_mode
            step_fn = lambda opt: make_sp_moe_lm_train_step(  # noqa: E731
                sp_ep_mesh, cfg, opt, mode=_mode
            )
            _ep = max(ep, 1)
            shard_fn = lambda p: dict(  # noqa: E731
                p, blocks=ep_shard_blocks(p["blocks"], _ep)
            )
            unshard_fn = lambda p: dict(  # noqa: E731
                p, blocks=ep_unshard_blocks(p["blocks"])
            )
        elif args.tensor_parallel > 1:
            # TP-INSIDE-EXPERTS (round 5; previously rejected): flat
            # (model, expert, data) mesh, each expert's FFN
            # Megatron-split over `model` (column-parallel up,
            # row-parallel down + one psum). Params stay in the
            # ep_shard_blocks layout — the model axis is a sharding
            # annotation on the FF dim.
            from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
            from tpu_dist_nn.train.lm_trainer import (
                make_ep_tp_moe_lm_train_step,
            )

            if args.batch_size % (max(ep, 1) * dp):
                raise ValueError(
                    f"--batch-size {args.batch_size} must be divisible "
                    f"by expert_parallel*data_parallel={max(ep, 1) * dp}"
                )
            ep_tp_mesh = build_mesh(MeshSpec(
                model=args.tensor_parallel, expert=max(ep, 1), data=dp
            ))
            global_mesh, global_span = ep_tp_mesh, max(ep, 1) * dp
            global_axes = "_data_expert_"
            step_fn = lambda opt: make_ep_tp_moe_lm_train_step(  # noqa: E731
                ep_tp_mesh, cfg, opt
            )
            _ep = max(ep, 1)
            shard_fn = lambda p: dict(  # noqa: E731
                p, blocks=ep_shard_blocks(p["blocks"], _ep)
            )
            unshard_fn = lambda p: dict(  # noqa: E731
                p, blocks=ep_unshard_blocks(p["blocks"])
            )
        elif ep > 1 or dp > 1:
            from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh

            if args.batch_size % (ep * dp):
                raise ValueError(
                    f"--batch-size {args.batch_size} must be divisible "
                    f"by expert_parallel*data_parallel={ep * dp}"
                )
            ep_mesh = build_mesh(MeshSpec(expert=ep, data=dp))
            global_mesh, global_span = ep_mesh, ep * dp
            global_axes = "_data_expert_"  # EP shards the batch over both
            step_fn = lambda opt: make_moe_lm_train_step(cfg, opt, ep_mesh)  # noqa: E731
            # The EP executor always expects the ep_shard_blocks layout,
            # including the degenerate ep=1 case (leading shard dim of 1).
            shard_fn = lambda p: dict(  # noqa: E731
                p, blocks=ep_shard_blocks(p["blocks"], ep)
            )
            unshard_fn = lambda p: dict(  # noqa: E731
                p, blocks=ep_unshard_blocks(p["blocks"])
            )
        else:
            step_fn = lambda opt: make_moe_lm_train_step(cfg, opt)  # noqa: E731
    else:
        cfg = TransformerConfig(**common)
        init_fn, eval_fn = init_transformer, evaluate_lm
        # Shared --zero1/--fsdp flag compatibility (one copy: the SP and
        # plain-DP branches both shard over the data axis).
        if args.zero1 and args.fsdp:
            raise ValueError("--fsdp already shards the optimizer "
                             "state; drop --zero1")
        if (args.zero1 or args.fsdp) and args.data_parallel < 2:
            raise ValueError(
                ("--fsdp" if args.fsdp else "--zero1")
                + " shards over the data axis: needs --data-parallel >= 2"
            )
        if args.stages > 1:
            if args.zero1 or args.fsdp:
                raise ValueError(
                    "--zero1/--fsdp compose with --data-parallel only "
                    "(state already lives per-stage in the pipeline)"
                )
            from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh

            if args.seq_parallel > 1:
                # Pipeline x sequence parallelism: blocks over `stage`,
                # each microbatch's sequence dim over `seq` (ring/
                # Ulysses attention inside the stage), batch over
                # `data`. Rows carry seq_len+1 tokens (the sp loss
                # masks position 0 instead of slicing).
                from tpu_dist_nn.train.lm_trainer import (
                    make_pipeline_sp_lm_train_step,
                )

                if (args.seq_len + 1) % args.seq_parallel:
                    raise ValueError(
                        f"--seq-len+1 ({args.seq_len + 1}) must be "
                        f"divisible by --seq-parallel {args.seq_parallel} "
                        "(rows carry the next-token target)"
                    )
                if args.batch_size % (args.microbatches * args.data_parallel):
                    raise ValueError(
                        f"--batch-size {args.batch_size} must be divisible "
                        f"by microbatches*data_parallel="
                        f"{args.microbatches * args.data_parallel}"
                    )
                pp_sp_mesh = build_mesh(MeshSpec(
                    stage=args.stages, seq=args.seq_parallel,
                    model=args.tensor_parallel, data=args.data_parallel,
                ))
                global_mesh, global_span = pp_sp_mesh, args.data_parallel
                global_axes = "_data_"
                schedule_handled = True  # pp x sp consumes --schedule itself
                _stages, _mb, _mode = args.stages, args.microbatches, args.sp_mode
                _sched, _tp = args.schedule, args.tensor_parallel
                _v = _default_virtual(args, _sched)
                step_fn = lambda opt: make_pipeline_sp_lm_train_step(  # noqa: E731
                    pp_sp_mesh, cfg, _stages, _mb, opt, mode=_mode,
                    schedule=_sched, num_virtual=_v, tensor_parallel=_tp,
                )
                _shard_b, _unshard_b = _lm_block_layout(
                    _sched, _stages, _v, cfg=cfg, tp=_tp
                )
                shard_fn = lambda p: dict(p, blocks=_shard_b(p["blocks"]))  # noqa: E731
                unshard_fn = lambda p: dict(p, blocks=_unshard_b(p["blocks"]))  # noqa: E731
            elif args.tensor_parallel > 1:
                # Pipeline x Megatron TP (x DP): previously library-only
                # (make_pipeline_lm_train_step(tensor_parallel=)), now a
                # flag. Layouts per schedule as in the pp x sp branch.
                from tpu_dist_nn.train.lm_trainer import (
                    make_pipeline_lm_train_step,
                )

                if args.batch_size % (args.microbatches * args.data_parallel):
                    raise ValueError(
                        f"--batch-size {args.batch_size} must be divisible "
                        f"by microbatches*data_parallel="
                        f"{args.microbatches * args.data_parallel}"
                    )
                pp_tp_mesh = build_mesh(MeshSpec(
                    stage=args.stages, model=args.tensor_parallel,
                    data=args.data_parallel,
                ))
                global_mesh, global_span = pp_tp_mesh, args.data_parallel
                global_axes = "_data_"
                schedule_handled = True  # pp x tp consumes --schedule itself
                _stages, _mb, _tp = (
                    args.stages, args.microbatches, args.tensor_parallel
                )
                _sched = args.schedule
                _v = _default_virtual(args, _sched)
                step_fn = lambda opt: make_pipeline_lm_train_step(  # noqa: E731
                    pp_tp_mesh, cfg, _stages, _mb, opt, schedule=_sched,
                    num_virtual=_v, tensor_parallel=_tp,
                )
                _shard_b, _unshard_b = _lm_block_layout(
                    _sched, _stages, _v, cfg=cfg, tp=_tp
                )
                shard_fn = lambda p: dict(p, blocks=_shard_b(p["blocks"]))  # noqa: E731
                unshard_fn = lambda p: dict(p, blocks=_unshard_b(p["blocks"]))  # noqa: E731
            else:
                mesh = build_mesh(
                    MeshSpec(stage=args.stages, data=args.data_parallel)
                )
                global_mesh, global_span = mesh, args.data_parallel
                global_axes = "_data_"
        elif args.seq_parallel > 1:
            from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
            from tpu_dist_nn.train.lm_trainer import (
                make_seq_parallel_lm_train_step,
            )

            # LM rows carry seq_len+1 tokens (inputs + next-token
            # targets); the sp loss feeds the full row to the ring.
            if (args.seq_len + 1) % args.seq_parallel:
                raise ValueError(
                    f"--seq-len+1 ({args.seq_len + 1}) must be divisible "
                    f"by --seq-parallel {args.seq_parallel} (rows carry "
                    "the next-token target)"
                )
            if args.batch_size % args.data_parallel:
                raise ValueError(
                    f"--batch-size {args.batch_size} must be divisible by "
                    f"--data-parallel {args.data_parallel}"
                )
            sp_mesh = build_mesh(
                MeshSpec(seq=args.seq_parallel, data=args.data_parallel)
            )
            global_mesh, global_span = sp_mesh, args.data_parallel
            global_axes = "_data_"
            if args.zero1 or args.fsdp:
                # SP x sharded optimizer state (round 4, previously
                # rejected): `params` is assigned below, before train_lm
                # invokes this factory.
                from tpu_dist_nn.parallel.zero import (
                    make_sp_sharded_lm_train_step,
                )

                _mode, _fsdp = args.sp_mode, args.fsdp
                step_fn = lambda opt: make_sp_sharded_lm_train_step(  # noqa: E731
                    sp_mesh, cfg, opt, params, mode=_mode,
                    shard_params=_fsdp,
                )
            else:
                step_fn = lambda opt: make_seq_parallel_lm_train_step(  # noqa: E731
                    sp_mesh, cfg, opt, mode=args.sp_mode
                )
        elif args.zero1 or args.fsdp:
            from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
            from tpu_dist_nn.parallel.zero import (
                make_fsdp_lm_train_step,
                make_zero_lm_train_step,
            )

            if args.batch_size % args.data_parallel:
                raise ValueError(
                    f"--batch-size {args.batch_size} must be divisible by "
                    f"--data-parallel {args.data_parallel}"
                )
            zero_mesh = build_mesh(MeshSpec(data=args.data_parallel))
            global_mesh, global_span = zero_mesh, args.data_parallel
            global_axes = "_data_"
            make = make_fsdp_lm_train_step if args.fsdp else make_zero_lm_train_step
            # `params` is assigned below, before train_lm invokes this.
            step_fn = lambda opt: make(zero_mesh, cfg, opt, params)  # noqa: E731

    # Fail fast with the other flag-compatibility checks — before corpus
    # load, param init, or checkpoint-dir creation do any work.
    if args.schedule != "gpipe" and not schedule_handled and (
        args.stages <= 1 or step_fn is not None
    ):
        raise ValueError(
            f"--schedule {args.schedule} applies to the pipelined dense LM "
            "only (--stages > 1, without --experts/--seq-parallel/"
            "--zero1/--fsdp)"
        )
    if args.sp_mode != "ring" and args.seq_parallel <= 1:
        raise ValueError(
            "--sp-mode requires --seq-parallel > 1 (it picks the "
            "sequence-parallel decomposition)"
        )

    text, source = load_corpus(args.corpus)
    tokens = encode(text)
    rows = lm_sequences(tokens, args.seq_len)
    split = max(1, int(len(rows) * 0.95))
    train_rows, eval_rows = rows[:split], rows[split:]
    import jax as _jax

    from tpu_dist_nn.data.feed import global_batch, shard_for_host

    nproc = _jax.process_count()
    globalize = None
    local_batch_size = args.batch_size
    if nproc > 1 and global_mesh is not None:
        from jax.sharding import PartitionSpec as _P

        from tpu_dist_nn.parallel.mesh import AXIS_DATA as _AD, AXIS_EXPERT as _AE

        _spec = (
            _P((_AD, _AE), None) if global_axes == "_data_expert_"
            else _P(_AD, None)
        )
        _gm = global_mesh
        if global_span % nproc == 0:
            # Multi-host data parallelism: per-process training stripe,
            # assembled into one globally-sharded batch per step;
            # --batch-size is GLOBAL.
            if args.batch_size % nproc:
                raise ValueError(
                    f"--batch-size {args.batch_size} must be divisible by "
                    f"{nproc} hosts"
                )
            local_batch_size = args.batch_size // nproc
            globalize = lambda b: global_batch(_gm, _spec, b)  # noqa: E731
            train_rows = shard_for_host(train_rows)
        else:
            # The batch axis does not span the hosts (e.g. --seq-parallel
            # across hosts with --data-parallel 1): every host feeds the
            # IDENTICAL full batch and cross-host parallelism comes from
            # the other mesh axes.
            log.info(
                "multi-host: batch axis spans %d-way (< %d hosts); feeding "
                "identical batches on every host, cross-host parallelism "
                "rides the other mesh axes", global_span, nproc,
            )
            globalize = lambda b: global_batch(  # noqa: E731
                _gm, _spec, b, assume_replicated=True
            )
    # (nproc > 1 with no global mesh: train_lm logs the replicated-
    # training warning — the single funnel for that condition.)
    params = init_fn(jax.random.key(args.seed), cfg)
    if shard_fn is not None:  # sharded-layout paths (EP, pipeline x sp)
        params = shard_fn(params)
    log.info(
        "tiny-transformer%s: %d params, corpus=%s, %d train rows, %d eval rows",
        f" (MoE x{args.experts})" if moe else "",
        num_params(params), source, len(train_rows), len(eval_rows),
    )
    train_cfg = LMTrainConfig(
        learning_rate=args.lr, steps=args.steps,
        batch_size=args.batch_size, seq_len=args.seq_len,
        clip_norm=args.clip_norm, warmup_steps=args.warmup_steps,
        lr_schedule=args.lr_schedule, weight_decay=args.weight_decay,
        grad_accum=args.grad_accum,
        steps_per_call=getattr(args, "steps_per_call", 1),
        log_every=getattr(args, "log_every", 50),
    )
    batches = lm_batches(
        train_rows, local_batch_size, seed=args.seed, epochs=None
    )
    checkpoints = None
    if args.checkpoint_dir:
        checkpoints = _make_checkpoint_manager(args)
    # --virtual-stages default depends on the schedule: interleaved is
    # pointless at v=1 (it IS the v>1 placement), while zb's documented
    # default is the classic contiguous v=1 placement — inheriting
    # interleaved's 2 would silently change the layout (and break
    # n_layers % (S*v) for valid zb runs).
    num_virtual = getattr(args, "virtual_stages", None)
    if num_virtual is None:
        num_virtual = 2 if args.schedule == "interleaved" else 1
    # Live telemetry for the whole run: training counters during the
    # loop, serving counters if --serve-generate follows. No engine
    # here, so /healthz is a bare liveness probe — gated by the drain
    # controller so a SIGTERM mid-serve flips it to NOT_SERVING.
    from tpu_dist_nn.serving.resilience import GracefulDrain

    drain = GracefulDrain(grace_seconds=args.drain_grace_seconds)
    metrics_server = _start_metrics_server(
        args, health_fn=drain.wrap_health(None)
    )
    t0 = time.monotonic()
    import contextlib

    trace_ctx = contextlib.nullcontext()
    if getattr(args, "profile_dir", None):
        from tpu_dist_nn.utils.profiling import capture_trace

        trace_ctx = capture_trace(args.profile_dir)
    with trace_ctx:
        params, history = train_lm(
            params, cfg, batches, train_cfg, mesh=mesh,
            num_stages=args.stages, num_microbatches=args.microbatches,
            checkpoints=checkpoints, step_fn=step_fn,
            # A step_fn branch that consumed --schedule already encodes
            # it; train_lm's own schedule validation applies to the
            # built-in pipelined path only.
            schedule="gpipe" if schedule_handled else args.schedule,
            globalize=globalize,
            num_virtual=num_virtual,
        )
    if getattr(args, "profile_dir", None):
        log.info("device trace written to %s", args.profile_dir)
    train_seconds = time.monotonic() - t0
    if unshard_fn is not None:
        params = unshard_fn(params)
    for h in history:
        log.info("step %d: loss %.4f (%.2fs)", h["step"], h["loss"], h["seconds"])
    held_out = len(eval_rows) >= args.batch_size
    if not held_out:
        log.warning(
            "eval split has %d rows < batch size %d; reporting metrics "
            "over the FULL dataset (includes training rows)",
            len(eval_rows), args.batch_size,
        )
    cap = getattr(args, "eval_batches", 0)
    eval_rows_used = eval_rows if held_out else rows
    avail_batches = len(eval_rows_used) // args.batch_size
    if cap > 0 and cap < avail_batches:
        # The cap changes WHAT the reported loss/perplexity measure —
        # make every truncated eval loudly comparable (ADVICE r5: the
        # old silent 512 default broke cross-round comparability).
        log.warning(
            "--eval-batches %d truncates the eval set (%d of %d "
            "batches evaluated); loss/perplexity cover a subset — "
            "compare eval_rows_used across runs",
            cap, cap, avail_batches,
        )
    eval_metrics = eval_fn(
        params, cfg, eval_rows_used,
        batch_size=args.batch_size,
        max_batches=cap if cap > 0 else None,
    )
    from tpu_dist_nn.utils.backend import (
        device_memory,
        device_report,
        param_devices,
    )

    report = {
        "device": device_report(),
        "param_devices": param_devices(params),
        "device_memory": device_memory(),
        "train_seconds": round(train_seconds, 2),
        "final_train_loss": history[-1]["loss"] if history else None,
        "eval_split": "held-out" if held_out else "full-dataset",
        **{k: round(v, 4) for k, v in eval_metrics.items()},
    }
    if args.metrics_out:
        _write_metrics_jsonl(
            args.metrics_out, history + [{"final_report": report}]
        )
    if args.sample_bytes > 0:
        import jax.numpy as jnp

        from tpu_dist_nn.data.text import decode as decode_text
        from tpu_dist_nn.models.generate import generate

        prompt = encode(args.prompt)[None, :]
        n = args.sample_bytes  # validated to fit before training
        if args.sample_pipeline_stages > 1:
            # Pipelined decode: generation IN the training placement —
            # blocks and KV caches sharded over the stage ring
            # (parallel/pp_generate.py; greedy).
            from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
            from tpu_dist_nn.parallel.pp_generate import (
                make_pipeline_generate,
            )
            from tpu_dist_nn.parallel.transformer_pipeline import (
                shard_blocks as _pp_shard_blocks,
            )

            spp = args.sample_pipeline_stages
            pp_mesh = build_mesh(MeshSpec(stage=spp))
            params_pp = dict(
                params, blocks=_pp_shard_blocks(params["blocks"], spp)
            )
            fn = make_pipeline_generate(
                pp_mesh, cfg, spp, n, temperature=args.temperature,
                top_k=args.top_k, top_p=args.top_p,
            )
            full = fn(
                params_pp, jnp.asarray(prompt),
                key=(jax.random.key(args.seed)
                     if args.temperature != 0 else None),
            )
            out = full[:, prompt.shape[1]:]
        elif args.sample_tensor_parallel > 1:
            # Megatron-sharded decode: heads + KV cache split over the
            # model axis (the trained params shard on the fly).
            from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
            from tpu_dist_nn.parallel.tensor_parallel import tp_shard_blocks
            from tpu_dist_nn.parallel.tp_generate import tp_generate

            tp_mesh = build_mesh(MeshSpec(model=args.sample_tensor_parallel))
            params_tp = dict(
                params,
                blocks=tp_shard_blocks(
                    params["blocks"], cfg, args.sample_tensor_parallel
                ),
            )
            out = tp_generate(
                tp_mesh, params_tp, cfg, jnp.asarray(prompt), n,
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, key=jax.random.key(args.seed),
            )
        else:
            # One compiled program for the whole prefill+decode loop —
            # eager dispatch would pay a host->device round trip per op.
            sample_fn = jax.jit(
                lambda p, t, k: generate(
                    p, cfg, t, n, temperature=args.temperature,
                    top_k=args.top_k, top_p=args.top_p, key=k,
                    eos_id=args.eos_id,
                )
            )
            out = sample_fn(
                params, jnp.asarray(prompt), jax.random.key(args.seed)
            )
        # Raw bytes decode UTF-8 with replacement, so the string may be
        # shorter than n bytes when multi-byte sequences collapse.
        sample_row = np.asarray(out[0])
        if args.eos_id is not None:
            # Trim at the stop token: everything after it is pad.
            hits = np.flatnonzero(sample_row == args.eos_id)
            if hits.size:
                sample_row = sample_row[:hits[0]]
        report["sample"] = decode_text(sample_row)
    if getattr(args, "serve_generate", None) is not None:
        return _serve_generate_and_wait(
            args, params, cfg, report, drain, metrics_server
        )
    print(json.dumps(report))
    _stop_metrics_server(metrics_server)
    return 0


def _endpoint_base(target: str) -> str:
    """Normalize a --target (host:port or URL) to a base URL — ONE
    copy shared by every verb that talks to a --metrics-port endpoint
    (`tdn metrics`, `tdn trace`), so scheme/trailing-slash handling
    cannot drift between them."""
    if "://" not in target:
        target = f"http://{target}"
    return target.rstrip("/")


def _endpoint_get(base: str, path: str, timeout: float,
                  method: str = "GET") -> bytes:
    """Fetch one endpoint route (GET by default; ``method="POST"`` for
    the state-changing admin verbs), mapping connection failures to
    the CLI's user-error convention (ValueError -> clean rc 2)."""
    import urllib.error
    import urllib.request

    url = base + path
    try:
        req = urllib.request.Request(
            url, data=(b"" if method == "POST" else None), method=method
        )
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.read()
    except urllib.error.HTTPError as e:
        # Non-200 admin/endpoint replies carry a JSON verdict in the
        # body (e.g. /router/drain on an unknown replica -> 404
        # {"draining": false}) — show it, not just the status line.
        try:
            detail = e.read().decode(errors="replace").strip()
        except OSError:
            detail = ""
        raise ValueError(
            f"{url} returned HTTP {e.code}"
            + (f": {detail}" if detail else "")
        ) from e
    except (urllib.error.URLError, OSError) as e:
        raise ValueError(f"could not fetch {url}: {e}") from e


def _aggregate_fleet(parsed_by_source: dict[str, dict]) -> dict:
    """Fold per-source /metrics scrapes into one fleet view: counter
    and histogram series SUM across sources (requests served by the
    fleet), gauges stay per-source (a queue depth summed across
    replicas hides which one is backlogged). Returns ``{"kinds":
    {name: kind}, "summed": {series: total}, "gauges": {series:
    {source: value}}}``."""
    kinds: dict[str, str] = {}
    for parsed in parsed_by_source.values():
        for k, v in parsed.items():
            if str(k).startswith("__type__:"):
                kinds[str(k).split(":", 1)[1]] = v
    summed: dict[str, float] = {}
    gauges: dict[str, dict[str, float]] = {}
    for source, parsed in parsed_by_source.items():
        for series, value in parsed.items():
            s = str(series)
            if s.startswith("__type__:"):
                continue
            family = s.split("{", 1)[0]
            # Histogram series (name_bucket/_sum/_count) resolve to
            # their family's declared kind.
            base_family = family
            for suffix in ("_bucket", "_sum", "_count"):
                if family.endswith(suffix) and family[: -len(suffix)] in kinds:
                    base_family = family[: -len(suffix)]
                    break
            kind = kinds.get(base_family, "gauge")
            if kind in ("counter", "histogram"):
                summed[s] = summed.get(s, 0.0) + float(value)
            else:
                gauges.setdefault(s, {})[source] = float(value)
    return {"kinds": kinds, "summed": summed, "gauges": gauges}


def cmd_metrics(args) -> int:
    """One-shot scrape of a running --metrics-port endpoint: fetch
    /metrics, pretty-print the tdn_* families (or dump raw text) —
    `curl | grep` without leaving the tool, and the quickest way to
    check coalescing efficiency on a live server. ``--aggregate``
    (against a ROUTER's endpoint) discovers the replica fleet via
    /router/replicas and folds router + every replica into one view:
    summed counters, per-replica gauges — fleet state in one command."""
    import urllib.error
    import urllib.request

    base = _endpoint_base(args.target)
    if getattr(args, "profile", False) and not args.aggregate:
        raise ValueError(
            "--profile rides the fleet fan-out: pass --aggregate too "
            "(for one process, use `tdn profile --target ...`)"
        )
    if getattr(args, "timeseries", None) and not args.aggregate:
        raise ValueError(
            "--timeseries rides the fleet fan-out: pass --aggregate "
            "too (for one process, curl GET /timeseries?family=...)"
        )
    if args.aggregate and getattr(args, "profile", False):
        # Fleet-wide /profile: per-stage self time merged across the
        # router (its router.forward lane included) and every replica —
        # "where does FLEET time go" as one table.
        from tpu_dist_nn.obs.collect import collect_fleet_profile
        from tpu_dist_nn.obs.profile import format_profile_table

        merged = collect_fleet_profile(base, timeout=args.timeout)
        srcs = merged.get("sources", {})
        print(f"fleet profile: {len(srcs)} endpoint(s) scraped, "
              f"{merged.get('traces', 0)} traces")
        for item in merged.get("unreachable", ()):
            print(f"  unreachable: {item['source']} ({item['error']})")
        if args.raw:
            print(json.dumps(merged))
        else:
            print(format_profile_table(merged))
            est = merged.get("merged_estimates", {})
            if est:
                print("  (merged estimates: p50 " + est.get("p50_s", "")
                      + "; p99/max " + est.get("p99_s", "") + ")")
        return 0
    text = _endpoint_get(base, "/metrics", args.timeout).decode()
    if args.aggregate:
        from tpu_dist_nn.obs import parse_prometheus_text

        try:
            replicas = json.loads(
                _endpoint_get(base, "/router/replicas", args.timeout)
            )
        except ValueError as e:
            raise ValueError(
                f"--aggregate needs a ROUTER metrics endpoint (its "
                f"/router/replicas admin route answered unexpectedly: {e})"
            ) from e
        parsed_by_source = {"router": parse_prometheus_text(text)}
        unreachable = []
        for rep in replicas:
            mt = rep.get("metrics_target")
            name = rep.get("target", mt)
            if not mt:
                unreachable.append((name, "no metrics_target registered"))
                continue
            try:
                rep_text = _endpoint_get(
                    _endpoint_base(mt), "/metrics", args.timeout
                ).decode()
            except ValueError as e:
                unreachable.append((name, str(e)))
                continue
            parsed_by_source[name] = parse_prometheus_text(rep_text)
        agg = _aggregate_fleet(parsed_by_source)
        print(f"fleet: router + {len(parsed_by_source) - 1} replica "
              f"endpoint(s) scraped")
        for name, why in unreachable:
            print(f"  unreachable: {name} ({why})")
        for s in sorted(agg["summed"]):
            print(f"[sum] {s} = {agg['summed'][s]:g}")
        for s in sorted(agg["gauges"]):
            for source in sorted(agg["gauges"][s]):
                print(f"[gauge] {s} @{source} = "
                      f"{agg['gauges'][s][source]:g}")
        # Fleet SLO verdict (ISSUE 11 satellite): /slo fanned out and
        # merged — burn rates recomputed from summed bad/total, never
        # averaged per process. Silent skip when no process declared
        # an objective (the common static-fleet shape).
        try:
            from tpu_dist_nn.obs.collect import collect_fleet_slo

            slo = collect_fleet_slo(base, timeout=args.timeout)
        except ValueError:
            slo = None
        if slo and slo.get("objectives"):
            print("fleet SLO (merged from "
                  + ", ".join(sorted({
                      s for o in slo["objectives"]
                      for s in o.get("sources", ())
                  })) + "):")
            for obj in slo["objectives"]:
                fast = obj["windows"].get("fast", {})
                slow = obj["windows"].get("slow", {})
                print(f"[slo] {obj['name']}: {obj.get('objective', '')} "
                      f"fast_burn={fast.get('burn_rate', 0):g} "
                      f"slow_burn={slow.get('burn_rate', 0):g} "
                      f"budget_left={obj['error_budget_remaining']:g}"
                      + (" BURNING" if obj.get("burning") else ""))
        # Fleet goodput verdict (ISSUE 14): /goodput fanned out and
        # merged — FLOP totals summed, fleet MFU recomputed over the
        # aggregate peak. Silent skip when no process has a tracker
        # attached (pre-goodput replicas).
        try:
            from tpu_dist_nn.obs.collect import collect_fleet_goodput

            gp = collect_fleet_goodput(base, timeout=args.timeout)
        except ValueError:
            gp = None
        if gp and gp["flops"]["total"] > 0:
            mfu = gp.get("mfu")
            mfu_s = f"{mfu:.4f}" if mfu is not None else "n/a"
            print(f"fleet goodput: mfu={mfu_s} "
                  f"pad_ratio={gp['pad_ratio']:.4f} "
                  f"useful_gflops={gp['flops']['useful'] / 1e9:.3f} "
                  f"pad_gflops={gp['flops']['pad'] / 1e9:.3f} "
                  f"prefix_saved_gflops="
                  f"{gp['flops']['prefix_saved'] / 1e9:.3f}")
            for source in sorted(gp.get("sources", {})):
                doc = gp["sources"][source]
                smfu = doc.get("mfu")
                print(f"[goodput] {source}: mfu="
                      + (f"{smfu:.4f}" if smfu is not None else "n/a")
                      + f" pad_ratio={doc.get('pad_ratio') or 0:.4f}"
                      + f" peak={doc.get('peak_source')}")
        if getattr(args, "timeseries", None):
            from tpu_dist_nn.obs.collect import collect_fleet_timeseries

            ts = collect_fleet_timeseries(
                base, family=args.timeseries, timeout=args.timeout
            )
            print(json.dumps(ts))
        return 0
    if args.raw:
        print(text, end="")
        return 0
    from tpu_dist_nn.obs import parse_prometheus_text

    parsed = parse_prometheus_text(text)
    kinds = {
        k.split(":", 1)[1]: v
        for k, v in parsed.items() if str(k).startswith("__type__:")
    }
    series = {
        k: v for k, v in parsed.items() if not str(k).startswith("__type__:")
    }
    for name in sorted(kinds):
        kind = kinds[name]
        if kind == "histogram":
            # One line per labeled series: count / sum / mean (the
            # bucket detail stays in --raw).
            prefix = name + "_count"
            for s in sorted(series):
                if s == prefix or s.startswith(prefix + "{"):
                    labels = s[len(prefix):]
                    count = series[s]
                    total = series.get(name + "_sum" + labels, 0.0)
                    mean = total / count if count else 0.0
                    print(
                        f"[histogram] {name}{labels} count={int(count)} "
                        f"sum={total:.6g} mean={mean:.6g}"
                    )
        else:
            for s in sorted(series):
                if s == name or s.startswith(name + "{"):
                    print(f"[{kind}] {s} = {series[s]:g}")
    try:
        with urllib.request.urlopen(
            base + "/healthz", timeout=args.timeout
        ) as resp:
            print(f"healthz: {resp.read().decode().strip()}")
    except urllib.error.HTTPError as e:
        # 503 carries the not-ready health JSON — that IS the report.
        print(f"healthz [{e.code}]: {e.read().decode().strip()}")
    except (urllib.error.URLError, OSError) as e:
        print(f"healthz: unavailable ({e})")
    return 0


def cmd_trace(args) -> int:
    """Pull a running endpoint's recorded request spans as a Chrome
    trace-event file: ``tdn trace --target host:metrics-port -o
    trace.json`` then open the file in Perfetto (ui.perfetto.dev) or
    ``chrome://tracing`` — where a ``jax.profiler`` capture of the same
    window can be overlaid for the request-to-device view.

    ``--aggregate`` (against a ROUTER's metrics endpoint) discovers the
    fleet via /router/replicas, pulls every process's /trace, and
    STITCHES them into one document — spans sharing a trace id land in
    one tree across per-process lanes, so a request's router hop and
    its serving replica's span subtree read as one timeline.
    ``--trace-id`` pulls just that trace (one slow exemplar, not the
    whole ring) in either mode."""
    base = _endpoint_base(args.target)
    if args.aggregate:
        if getattr(args, "since", None) is not None:
            # The stitcher pulls whole rings per process and carries no
            # per-source cursor — a silently ignored --since would look
            # like an active incremental poll (fail-fast convention).
            raise ValueError(
                "--since is a single-endpoint incremental cursor and "
                "does not combine with --aggregate (the fleet stitch "
                "pulls every process's ring)"
            )
        from tpu_dist_nn.obs.collect import collect_fleet_trace

        doc = collect_fleet_trace(
            base, timeout=args.timeout, limit=args.limit,
            trace_id=args.trace_id,
        )
        events = doc["traceEvents"]
        body = json.dumps(doc).encode()
        meta = doc.get("metadata", {})
        with open(args.out, "wb") as f:
            f.write(body)
        spans = [e for e in events if e.get("ph") == "X"]
        traces = {
            e["args"]["trace_id"] for e in spans
            if "trace_id" in e.get("args", {})
        }
        print(json.dumps({
            "out": args.out,
            "stitched_sources": meta.get("stitched_sources"),
            "lanes": meta.get("lanes"),
            "unreachable": meta.get("unreachable"),
            "events": len(events),
            "spans": len(spans),
            "traces": len(traces),
            "deduped_events": meta.get("deduped_events"),
            "trace_id_filter": args.trace_id,
            "open_with": "https://ui.perfetto.dev or chrome://tracing",
        }))
        return 0
    path = "/trace"
    params = []
    if args.limit is not None:
        params.append(f"limit={args.limit}")
    if args.trace_id is not None:
        params.append(f"trace_id={args.trace_id}")
    if getattr(args, "since", None) is not None:
        params.append(f"since={args.since}")
    if params:
        path += "?" + "&".join(params)
    body = _endpoint_get(base, path, args.timeout)
    try:
        doc = json.loads(body)
        events = doc["traceEvents"]
    except (ValueError, KeyError, TypeError) as e:
        raise ValueError(
            f"{base}{path} did not return a Chrome trace-event "
            f"document: {e}"
        ) from e
    with open(args.out, "wb") as f:
        f.write(body)
    spans = [e for e in events if e.get("ph") == "X"]
    traces = {e["args"]["trace_id"] for e in spans if "trace_id" in e.get("args", {})}
    # Slowest-span summary by SELF time (child time subtracted): a slow
    # `fetch` must not inflate its `rpc.Process` parent's row and hide
    # the real culprit. Containment nesting + interval subtraction live
    # in obs/profile (the same math /profile serves).
    from tpu_dist_nn.obs.profile import SpanRecord, compute_self_times

    records = [
        SpanRecord(
            e["name"], e["args"].get("trace_id", ""),
            e["args"].get("span_id", f"_anon{i}"),
            e["args"].get("parent_id"),
            e["ts"] / 1e6, e.get("dur", 0) / 1e6,
        )
        for i, e in enumerate(spans) if "args" in e
    ]
    selfs = compute_self_times(records)
    by_self = sorted(
        records, key=lambda r: selfs.get(r.span_id, 0.0), reverse=True
    )[:3]
    print(json.dumps({
        "out": args.out,
        "events": len(events),
        "spans": len(spans),
        "traces": len(traces),
        "slowest": [
            {"name": r.name,
             "self_ms": round(selfs.get(r.span_id, 0.0) * 1e3, 3),
             "dur_ms": round(r.dur * 1e3, 3),
             "trace_id": r.trace_id or None}
            for r in by_self
        ],
        "slowest_ranked_by": "self_time",
        # Pass back as --since on the next poll: only spans that
        # finished after this cursor come down the wire.
        "cursor": doc.get("cursor"),
        "open_with": "https://ui.perfetto.dev or chrome://tracing",
    }))
    return 0


def cmd_profile(args) -> int:
    """Pull a running endpoint's per-stage self-time breakdown — the
    "where does the time go" table (``tdn profile --target
    host:metrics-port``) — and, with ``--capture-seconds``, an
    on-demand ``jax.profiler`` device trace zip from
    ``/debug/profile`` (open the extracted directory in TensorBoard /
    Perfetto alongside the request spans from ``tdn trace``)."""
    from tpu_dist_nn.obs.profile import format_profile_table

    base = _endpoint_base(args.target)
    path = "/profile"
    params = []
    if args.window is not None:
        params.append(f"window={args.window}")
    if args.top is not None:
        params.append(f"top={args.top}")
    if params:
        path += "?" + "&".join(params)
    body = _endpoint_get(base, path, args.timeout)
    try:
        doc = json.loads(body)
        doc["methods"]
    except (ValueError, KeyError, TypeError) as e:
        raise ValueError(
            f"{base}{path} did not return a /profile document: {e}"
        ) from e
    if args.json:
        print(json.dumps(doc))
    else:
        print(format_profile_table(doc))
    if args.capture_seconds is not None:
        # Device capture AFTER the breakdown (the table tells you
        # whether a capture is even worth the pause): the artifact is
        # the zipped TensorBoard-format profiler directory. Fetched
        # directly (not via _endpoint_get): the endpoint's graceful
        # degrades arrive as HTTP 503/409 with a JSON reason in the
        # BODY, and that reason — not a bare status line — is the
        # user-facing error.
        import urllib.error
        import urllib.request

        url = f"{base}/debug/profile?seconds={args.capture_seconds}"
        try:
            with urllib.request.urlopen(
                # The HTTP wait IS the capture window plus writeout.
                url, timeout=args.timeout + float(args.capture_seconds) + 30.0,
            ) as resp:
                cap = resp.read()
        except urllib.error.HTTPError as e:
            body = e.read().decode(errors="replace").strip()
            raise ValueError(
                f"device capture unavailable (HTTP {e.code}): {body}"
            ) from e
        except (urllib.error.URLError, OSError) as e:
            raise ValueError(f"could not fetch {url}: {e}") from e
        if not cap.startswith(b"PK"):
            raise ValueError(
                f"device capture unavailable: {cap.decode(errors='replace').strip()}"
            )
        with open(args.capture_out, "wb") as f:
            f.write(cap)
        print(json.dumps({
            "device_capture": args.capture_out,
            "seconds": args.capture_seconds,
            "bytes": len(cap),
            "open_with": "unzip, then tensorboard --logdir <dir> or "
                         "ui.perfetto.dev",
        }))
    return 0


def _fmt_age(seconds: float) -> str:
    if seconds < 90:
        return f"{seconds:.0f}s"
    if seconds < 5400:
        return f"{seconds / 60:.0f}m"
    return f"{seconds / 3600:.1f}h"


def cmd_incident(args) -> int:
    """Browse a serving endpoint's flight-recorder store (``tdn
    incident ls|show|pull --target host:metrics-port``): list captured
    incident bundles, print one bundle's manifest, or download the
    zip for offline digging (its trace.json opens in Perfetto, its
    logs/timeseries/slo sections are plain JSON)."""
    import urllib.parse

    base = _endpoint_base(args.target)
    if args.action == "ls":
        doc = json.loads(_endpoint_get(base, "/incidents", args.timeout))
        incidents = doc.get("incidents", [])
        print(f"{len(incidents)} incident(s) in {doc.get('directory')} "
              f"(max {doc.get('max_incidents')}, "
              f"{doc.get('captured_total', 0)} captured this boot)")
        now = time.time()
        for m in incidents:
            if "error" in m and "trigger" not in m:
                print(f"  {m.get('incident_id', '?'):<44} {m['error']}")
                continue
            age = _fmt_age(max(now - float(m.get("captured_at", now)), 0))
            size = int(m.get("bytes", 0))
            reason = str(m.get("reason", ""))[:60]
            print(f"  {m.get('incident_id', '?'):<44} "
                  f"{m.get('trigger', '?'):<22} {age:>5} ago "
                  f"{size / 1024:>7.1f}KB  {reason}")
        return 0
    if not args.id:
        raise ValueError(
            f"tdn incident {args.action} needs an incident id "
            "(see `tdn incident ls`)"
        )
    if args.action == "show":
        doc = json.loads(_endpoint_get(base, "/incidents", args.timeout))
        for m in doc.get("incidents", []):
            if m.get("incident_id") == args.id:
                print(json.dumps(m, indent=2))
                return 0
        raise ValueError(f"no incident {args.id!r} on {base} "
                         "(see `tdn incident ls`)")
    # pull
    data = _endpoint_get(
        base, "/incidents/get?id=" + urllib.parse.quote(args.id, safe=""),
        args.timeout,
    )
    if not data.startswith(b"PK"):
        raise ValueError(
            f"{base}/incidents/get did not return a bundle zip: "
            f"{data[:200].decode(errors='replace')}"
        )
    out = args.out or f"{args.id}.zip"
    with open(out, "wb") as f:
        f.write(data)
    print(json.dumps({
        "out": out, "incident_id": args.id, "bytes": len(data),
        "open_with": "unzip; trace.json loads in ui.perfetto.dev",
    }))
    return 0


def cmd_debug(args) -> int:
    """Manual diagnostic capture (``tdn debug bundle --target
    host:metrics-port``): GET /debug/bundle on a running endpoint —
    against a router this captures the WHOLE fleet (every replica's
    bundle embedded, traces stitched) — and save the zip locally.
    The on-demand twin of the detector-triggered captures."""
    import io as _io
    import urllib.parse
    import zipfile as _zipfile

    # argparse fixes args.what to "bundle" today; the positional keeps
    # the verb extensible (tdn debug <what>) without a breaking rename.
    base = _endpoint_base(args.target)
    params = []
    if args.no_fleet:
        params.append("fleet=0")
    if args.reason:
        params.append("reason=" + urllib.parse.quote(args.reason, safe=""))
    path = "/debug/bundle" + ("?" + "&".join(params) if params else "")
    # The HTTP wait covers the capture itself (a router fans out to
    # every replica within its fleet timeout) — give it headroom.
    data = _endpoint_get(base, path, args.timeout + 30.0)
    if not data.startswith(b"PK"):
        raise ValueError(
            f"{base}{path} did not return a bundle zip: "
            f"{data[:200].decode(errors='replace')}"
        )
    with open(args.out, "wb") as f:
        f.write(data)
    summary = {"out": args.out, "bytes": len(data)}
    try:
        with _zipfile.ZipFile(_io.BytesIO(data)) as z:
            manifest = json.loads(z.read("manifest.json"))
        summary["incident_id"] = manifest.get("incident_id")
        summary["sections"] = manifest.get("sections")
        replicas = manifest.get("replicas")
        if replicas is not None:
            summary["replicas"] = [
                {k: r[k] for k in ("target", "error") if k in r}
                for r in replicas
            ]
    except (KeyError, ValueError, _zipfile.BadZipFile):
        summary["warning"] = "bundle has no readable manifest.json"
    summary["open_with"] = "unzip; trace.json loads in ui.perfetto.dev"
    print(json.dumps(summary))
    return 0


def cmd_top(args) -> int:
    """Live fleet dashboard (``tdn top --target host:metrics-port``):
    polls the router's /router/replicas + every endpoint's /metrics,
    /timeseries, and /slo on an interval and renders per-replica rps,
    p50/p99, decode-slot occupancy, pending rows, breaker state,
    prefix-cache hit ratio, SLO budget, and request-rate sparklines.
    Against a single server's endpoint it shows that process alone."""
    from tpu_dist_nn.obs.top import run_top

    if args.interval <= 0:
        raise ValueError(f"--interval must be > 0, got {args.interval}")
    color = None
    if args.no_color:
        color = False
    return run_top(
        _endpoint_base(args.target), interval=args.interval,
        iterations=args.iterations, timeout=args.timeout, color=color,
    )


def cmd_warmup(args) -> int:
    """Precompile the serving bucket ladder AHEAD of traffic: bring the
    engine up, run the pow2 row buckets up to --rows, report what got
    warm. With a persistent XLA compile cache configured
    (JAX_COMPILATION_CACHE_DIR), the compiles land on disk and a later
    `tdn up --grpc-port` on the same model skips them entirely;
    without one, this is the in-process warm `--serve-warm-rows`
    performs at serve time (reported so the operator knows which).

    ``--lm`` warms the GENERATION path instead: the continuous
    scheduler's prefill-at-slot and slot-step kernels for the given LM
    shape (compiles key on shapes, not weights, so warming with random
    params pre-warms the real server)."""
    import jax

    if getattr(args, "lm", False):
        from tpu_dist_nn.models.transformer import (
            TransformerConfig,
            init_transformer,
        )
        from tpu_dist_nn.serving.continuous import ContinuousScheduler

        metrics_server = _start_metrics_server(args)
        t0 = time.monotonic()
        cfg = TransformerConfig(
            vocab_size=256, d_model=args.d_model, n_heads=args.heads,
            n_layers=args.layers, d_ff=4 * args.d_model,
            max_seq_len=args.seq_len,
        )
        params = init_transformer(jax.random.key(0), cfg)
        sched = ContinuousScheduler(
            params, cfg, slots=args.gen_slots,
            prompt_len=args.serve_prompt_len,
            max_new_tokens=args.serve_new_tokens,
            prefix_cache_blocks=args.prefix_cache_blocks,
            prefill_chunk=args.prefill_chunk,
        )
        warmed = sched.warm()
        sched.close()
        cache_dir = jax.config.jax_compilation_cache_dir
        print(json.dumps({
            "warmed_kernels": warmed,
            "gen_slots": args.gen_slots,
            "prompt_len": args.serve_prompt_len,
            "max_new_tokens": args.serve_new_tokens,
            "prefix_cache_blocks": args.prefix_cache_blocks,
            "prefill_chunk": args.prefill_chunk,
            "seconds": round(time.monotonic() - t0, 3),
            "persistent_cache_dir": cache_dir,
            "persists_across_processes": bool(cache_dir),
        }))
        _stop_metrics_server(metrics_server)
        return 0
    if not args.config:
        raise ValueError("--config is required (or pass --lm to warm "
                         "the generation kernels instead)")
    metrics_server = _start_metrics_server(args)
    t0 = time.monotonic()
    engine = _engine_from_args(args)
    warmed = engine.warm_buckets(args.rows)
    cache_dir = jax.config.jax_compilation_cache_dir
    print(json.dumps({
        "warmed_buckets": warmed,
        "warm_bucket_count": engine.warm_bucket_count,
        "max_rows": args.rows,
        "seconds": round(time.monotonic() - t0, 3),
        "persistent_cache_dir": cache_dir,
        "persists_across_processes": bool(cache_dir),
        "placement": engine.placement(),
    }))
    engine.down()
    _stop_metrics_server(metrics_server)
    return 0


def cmd_oracle(args) -> int:
    """Single-process float64 baseline (scripts/manual_nn.py:88-99)."""
    from tpu_dist_nn.core.schema import load_examples, load_model
    from tpu_dist_nn.testing.oracle import oracle_forward

    model = load_model(args.config)
    x, _ = load_examples(args.inputs)
    total = 0.0
    for example in x:
        t0 = time.monotonic()
        oracle_forward(model, example)
        dt = time.monotonic() - t0
        total += dt
        print(f"Inference time: {dt:.4f} seconds")
    print(f"Total inference time: {total:.4f} seconds")
    print(f"Average inference time: {total / len(x):.4f} seconds")
    return 0


def cmd_import_torch(args) -> int:
    """Convert a torch state dict (.pt) to the public model JSON —
    the reference's commented-out exporter made real
    (generate_mnist_pytorch.py:68-103)."""
    try:
        import torch
    except ImportError as e:
        raise ValueError(
            f"import-torch needs pytorch installed ({e}); pip install torch"
        ) from e

    from tpu_dist_nn.core.schema import save_model
    from tpu_dist_nn.interop import model_from_torch_state_dict

    state = torch.load(args.state_dict, map_location="cpu", weights_only=True)
    if isinstance(state, dict) and "state_dict" in state:
        state = state["state_dict"]  # common checkpoint wrapper
    acts = args.activations.split(",") if args.activations else None
    model = model_from_torch_state_dict(state, acts)
    save_model(model, args.out)
    log.info(
        "imported %d dense layers (%s) to %s",
        len(model.layers), "-".join(map(str, model.layer_sizes)), args.out,
    )
    return 0


def cmd_import_keras(args) -> int:
    """Convert a saved Keras model (.keras/.h5) to the public model
    JSON — the reference's commented-out TF exporter made real
    (generate_mnist_tensorflow.py:41-78, notebook cell 10)."""
    from tpu_dist_nn.core.schema import save_model
    from tpu_dist_nn.interop import model_from_keras_file

    acts = args.activations.split(",") if args.activations else None
    model = model_from_keras_file(args.model, activations=acts)
    save_model(model, args.out)
    log.info(
        "imported %d dense layers (%s) to %s",
        len(model.layers), "-".join(map(str, model.layer_sizes)), args.out,
    )
    return 0


def _load_tdnlint():
    """Load tools/tdnlint by path: the analyzer lives next to the
    package in a repo checkout (it is a development gate, not a
    runtime dependency, so it is not shipped inside tpu_dist_nn)."""
    if "tdnlint" in sys.modules:
        return sys.modules["tdnlint"]
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pkg = os.path.join(root, "tools", "tdnlint", "__init__.py")
    if not os.path.exists(pkg):
        raise FileNotFoundError(
            "tools/tdnlint not found next to the tpu_dist_nn package — "
            "`tdn lint` runs from a repository checkout"
        )
    spec = importlib.util.spec_from_file_location(
        "tdnlint", pkg,
        submodule_search_locations=[os.path.dirname(pkg)],
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["tdnlint"] = mod
    spec.loader.exec_module(mod)
    return mod


def cmd_replay(args) -> int:
    """The scenario engine (``tdn replay``, docs/OBSERVABILITY.md
    "Capture & replay" / docs/ROBUSTNESS.md "Chaos-load matrix"):

    * ``tdn replay --scenario scenarios/X.json`` — run one declarative
      scenario cell (workload x faults x fleet events) on a self-hosted
      loopback fleet, score it with the real SLOTracker, print the
      machine-readable verdict. Exit 0 on pass, 2 on fail.
    * ``tdn replay --scenario-dir scenarios/`` — the whole matrix;
      exit 2 unless every cell passes.
    * ``tdn replay --scenario X.json --target host:port`` — remote
      load-test mode: fire the scenario's WORKLOAD at a live fleet.
      Fault injection, chaos events, and SLO scoring are loopback-only
      and are disabled; the report carries the client-observed outcome
      plus a caveat, and ``passed`` is null (score SLOs from the
      target's own ``/metrics``).
    * ``tdn replay --bundle incident.zip --target host:port`` —
      extract the WorkloadTrace from a captured incident bundle and
      fire it at a LIVE target at ``--speed`` multiples.
    * ``tdn replay --trace trace.json --target host:port`` — replay a
      saved WorkloadTrace file.
    * ``tdn replay --generate diurnal -o trace.json`` — emit a seeded
      synthetic workload as a WorkloadTrace JSON (no target needed).
    """
    from tpu_dist_nn.obs import replay as R

    def emit(doc) -> None:
        text = json.dumps(doc, indent=2 if args.pretty else None)
        if args.out:
            with open(args.out, "w") as f:
                f.write(text)
            print(json.dumps({"out": args.out,
                              "passed": doc.get("passed")}))
        else:
            print(text)

    if args.scenario or args.scenario_dir:
        paths = ([args.scenario] if args.scenario
                 else R.scenario_paths(args.scenario_dir))
        if not paths:
            raise ValueError(f"no scenario specs in {args.scenario_dir}")
        verdicts = []
        for path in paths:
            if args.target:
                v = R.run_scenario_remote(
                    R.load_scenario(path), args.target,
                    seed=args.seed, speed=args.speed,
                    quick_scale=args.quick_scale,
                )
            else:
                v = R.run_scenario_file(
                    path, seed=args.seed, speed=args.speed,
                    quick_scale=args.quick_scale,
                )
            verdicts.append(v)
            if len(paths) > 1:
                print(json.dumps({
                    "scenario": v["scenario"], "passed": v["passed"],
                    "duration_s": v["duration_s"],
                    "requests": v["replay"]["requests"],
                    "ok": v["replay"]["ok"],
                }))
        if len(verdicts) == 1:
            doc = verdicts[0]
        elif args.target:
            # Remote load-test runs carry no verdict to aggregate.
            doc = {"scenarios": len(verdicts), "mode": "remote",
                   "passed": None, "verdicts": verdicts}
        else:
            doc = {
                "scenarios": len(verdicts),
                "passed": all(v["passed"] for v in verdicts),
                "pass_ratio": round(
                    sum(v["passed"] for v in verdicts) / len(verdicts), 4
                ),
                "verdicts": verdicts,
            }
        emit(doc)
        return 0 if doc["passed"] in (True, None) else 2

    if args.generate:
        gen_args = json.loads(args.generator_args or "{}")
        wl = R.make_workload(args.generate, seed=args.seed or 0,
                             **gen_args)
        if args.out:
            wl.save(args.out)
            print(json.dumps({"out": args.out, **wl.mix()}))
        else:
            print(wl.to_json())
        return 0

    if args.bundle:
        wl = R.trace_from_bundle(args.bundle)
    elif args.trace:
        wl = R.WorkloadTrace.load(args.trace)
    else:
        raise ValueError(
            "tdn replay needs one of --scenario/--scenario-dir/"
            "--bundle/--trace/--generate"
        )
    if not args.target:
        raise ValueError("--bundle/--trace replay needs --target")
    report = R.replay(
        wl, args.target, speed=args.speed or 1.0,
        dim=args.dim, prompt_len=args.prompt_len,
        vocab_size=args.vocab_size, timeout=args.timeout,
    )
    emit(report)
    return 0


def cmd_lint(args) -> int:
    tdnlint = _load_tdnlint()
    argv = list(args.paths or ())
    for rule in args.rule or ():
        argv += ["--rule", rule]
    if args.baseline is not None:
        argv += ["--baseline", args.baseline]
    if args.update_baseline:
        argv.append("--update-baseline")
    if args.list_rules:
        argv.append("--list-rules")
    if args.lint_json:
        argv.append("--json")
    return tdnlint.main(argv)


def cmd_doctor(args) -> int:
    """Environment self-check: what a support request needs up front —
    backend, devices, native library, kernel lowering, oracle parity.
    The operational analogue of the reference's readiness poll
    (run_grpc_fcnn.py:157-172), extended to the whole stack."""
    import jax

    report = {}
    report["backend"] = jax.default_backend()
    report["device_kind"] = jax.devices()[0].device_kind
    report["devices"] = [str(d) for d in jax.devices()]
    report["process_count"] = jax.process_count()

    from tpu_dist_nn.native.loader import get_library

    report["native_library"] = get_library() is not None

    import numpy as _np

    from tpu_dist_nn.models.fcnn import forward, init_fcnn, spec_from_params
    from tpu_dist_nn.testing.oracle import oracle_forward_batch

    params = init_fcnn(jax.random.key(0), [16, 8, 4])
    model = spec_from_params(params, ["relu", "softmax"])
    x = _np.random.default_rng(0).uniform(0, 1, (4, 16)).astype(_np.float32)
    got = _np.asarray(jax.jit(forward)(params, x))
    want = oracle_forward_batch(model, x)
    err = float(_np.max(_np.abs(got - want)))
    report["oracle_max_abs_err"] = err
    report["oracle_parity"] = err < (5e-3 if report["backend"] == "tpu" else 1e-5)

    try:
        from tpu_dist_nn.kernels.fused_dense import fused_dense

        import jax.numpy as jnp

        out = fused_dense(
            jnp.ones((8, 16)), jnp.ones((16, 8)), jnp.zeros((8,)),
            activation="relu",
        )
        jax.block_until_ready(out)
        report["pallas_kernels"] = "ok"
    except Exception as e:  # noqa: BLE001 — a self-check reports, never raises
        report["pallas_kernels"] = f"failed: {type(e).__name__}: {e}"

    if getattr(args, "serving", False):
        # Loopback gRPC round trip: server + client through the real
        # wire codec against a tiny engine, bound to 127.0.0.1 only (a
        # self-check must not expose an unauthenticated endpoint on the
        # network) on an ephemeral port.
        eng = server = client = None
        try:
            import numpy as _np2

            from tpu_dist_nn.api.engine import Engine
            from tpu_dist_nn.serving import GrpcClient, serve_engine
            from tpu_dist_nn.testing.factories import random_model

            m = random_model([8, 6, 4], seed=0)
            eng = Engine.up(m, [2])
            server, port = serve_engine(eng, 0, host="127.0.0.1")
            client = GrpcClient(f"127.0.0.1:{port}")
            xs = _np2.random.default_rng(1).uniform(0, 1, (3, 8))
            remote = client.process(xs)
            local = eng.infer(xs)
            ok = bool(_np2.allclose(remote, local, rtol=1e-6))
            report["serving"] = {"port": port, "round_trip": ok}
        except Exception as e:  # pragma: no cover - environment-specific
            # round_trip=False so a broken serving stack fails the
            # health verdict — that is the point of the flag.
            report["serving"] = {
                "round_trip": False, "error": f"{type(e).__name__}: {e}"
            }
        finally:
            if client is not None:
                client.close()
            if server is not None:
                server.stop(grace=0.2)
            if eng is not None:
                eng.down()

    if getattr(args, "multichip", None):
        # Budgeted local replica of the driver's multi-chip dry run
        # (VERDICT r1: the dryrun timed out at the driver — this catches
        # budget regressions before the round ends). Runs in a
        # SUBPROCESS so the virtual-CPU platform forcing can't collide
        # with this process's backend, and a hang is bounded by the
        # budget instead of wedging the doctor.
        import subprocess
        import sys as _sys
        import time as _time

        n = int(args.multichip)
        budget = float(args.multichip_budget)
        code = (
            "from tpu_dist_nn.testing.dryrun import dryrun_multichip\n"
            f"dryrun_multichip({n})\n"
        )
        t0 = _time.monotonic()
        verdict = {"n_devices": n, "budget_s": budget}
        try:
            proc = subprocess.run(
                [_sys.executable, "-c", code],
                capture_output=True, text=True, timeout=budget,
            )
            verdict["elapsed_s"] = round(_time.monotonic() - t0, 1)
            verdict["ok"] = proc.returncode == 0
            if proc.returncode != 0:
                verdict["tail"] = proc.stderr[-1500:]
        except subprocess.TimeoutExpired as e:
            verdict["elapsed_s"] = round(_time.monotonic() - t0, 1)
            verdict["ok"] = False
            verdict["tail"] = (
                f"TIMEOUT after {budget:.0f}s (the driver would record "
                f"rc=124): {((e.stderr or b'')[-500:])!r}"
            )
        report["multichip"] = verdict

    report["healthy"] = bool(
        report["oracle_parity"] and report["devices"]
        and report["pallas_kernels"] == "ok"
        and report.get("serving", {}).get("round_trip", True)
        and report.get("multichip", {}).get("ok", True)
    )
    print(json.dumps(report, indent=2))
    return 0 if report["healthy"] else 1


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="tdn", description=__doc__)
    parser.add_argument(
        "--platform", choices=["auto", "cpu", "tpu"],
        default=os.environ.get("TDN_PLATFORM", "auto"),
        help="where device work runs: auto (default) takes JAX's own "
             "resolution and logs it; tpu exits non-zero unless JAX "
             "resolved a TPU; cpu pins the host backend. Nothing "
             "probes or falls back (env: TDN_PLATFORM)",
    )
    parser.add_argument(
        "--log-json", action="store_true",
        default=os.environ.get("TDN_LOG_JSON", "") == "1",
        help="emit logs as one JSON object per line (structured "
             "records keep their event/fields; everything else "
             "degrades to {'event': message}) — env: TDN_LOG_JSON=1",
    )
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("up", help="validate, place, compile (orchestrator)")
    _add_up_args(p)
    _add_multihost_args(p)
    p.add_argument("--probe-latency", action="store_true",
                   help="report p50/p90/p99 pipeline step latency "
                        "(the BASELINE per-stage metric)")
    p.add_argument("--serve", action="store_true",
                   help="stay up until Ctrl-C, then tear down "
                        "(the reference orchestrator's supervisor loop)")
    p.add_argument("--grpc-port", type=int, default=None,
                   help="also expose the reference's LayerService gRPC "
                        "endpoint on this port (wire-compatible with "
                        "run_grpc_inference.py; its stage-0 port is 5101) "
                        "and stay up until Ctrl-C")
    p.add_argument("--serve-warm-rows", type=int, default=64,
                   help="precompile request-coalescing bucket shapes up "
                        "to this many rows before opening the port "
                        "(0 disables)")
    p.add_argument("--serve-seconds", type=float, default=None,
                   help="serve for N seconds then tear down (default: "
                        "until interrupted; bounds --serve/--grpc-port "
                        "runs for drivers and tests)")
    p.add_argument("--max-pending-rows", type=int, default=None,
                   help="admission-control watermark: a request that "
                        "would queue past this many pending rows is shed "
                        "with RESOURCE_EXHAUSTED instead of backlogging "
                        "unboundedly (default: unbounded; "
                        "docs/ROBUSTNESS.md)")
    p.add_argument("--class-watermarks", default=None, metavar="SPEC",
                   help="per-SLO-class shed fractions of "
                        "--max-pending-rows, e.g. "
                        "'critical=1.0,standard=1.0,best_effort=0.5' "
                        "(the default): best_effort sheds first, the "
                        "headroom above its fraction stays reserved "
                        "for the paging classes (docs/ROBUSTNESS.md "
                        "'Degradation ladder')")
    p.add_argument("--drain-grace-seconds", type=float, default=5.0,
                   help="graceful-drain window on SIGTERM: /healthz "
                        "flips NOT_SERVING, new RPCs are refused, and "
                        "in-flight requests get this long to finish "
                        "before exit (docs/ROBUSTNESS.md)")
    p.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                   help="also expose /metrics (Prometheus text), "
                        "/healthz (Engine.health as JSON), and /trace "
                        "(Chrome trace-event spans) on this port "
                        "(0 = ephemeral, printed as a JSON line)")
    p.add_argument("--trace-sample-rate", type=float, default=None,
                   metavar="RATE",
                   help="head-sampling rate for request-scoped tracing "
                        "in [0, 1]: 1 traces every request (default), "
                        "0 disables recording entirely (env: "
                        "TDN_TRACE_SAMPLE_RATE)")
    _add_slo_args(p)
    _add_incident_args(p)
    p.set_defaults(fn=cmd_up)

    p = sub.add_parser("infer", help="run inference (client)")
    p.add_argument("input_index", nargs="?", type=int, default=None)
    _add_up_args(p, config_required=False)
    _add_multihost_args(p)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--target",
                   help="host:port of a running `tdn up --grpc-port` "
                        "server: act as a pure gRPC client (the "
                        "reference client's role; no --config needed)")
    p.add_argument("--port", type=int, default=None,
                   help="with no --target: compat no-op (no sockets in "
                        "the local data path); shorthand for "
                        "--target 127.0.0.1:PORT otherwise")
    p.add_argument("--timeout", type=float, default=None,
                   help="per-RPC timeout for --target (default 30s); "
                        "compat no-op locally")
    p.add_argument("--retry-max-attempts", type=int, default=None,
                   help="with --target: total attempts per RPC under the "
                        "client retry policy (jittered backoff on "
                        "UNAVAILABLE/DEADLINE_EXCEEDED within --timeout; "
                        "1 = no retries, default 3; docs/ROBUSTNESS.md)")
    p.add_argument("--session-key",
                   help="with --target: send this x-tdn-session key on "
                        "every RPC so a multi-replica router (tdn "
                        "router) pins the session to one replica; a "
                        "single server ignores it (docs/SCALING.md)")
    p.add_argument("--slo-class", default=None,
                   choices=["critical", "standard", "best_effort"],
                   help="with --target: send this x-tdn-class SLO "
                        "class on every RPC — queue priority and shed "
                        "watermark at the server, hedging exemption "
                        "for best_effort at the router (default: no "
                        "header = standard; docs/ROBUSTNESS.md "
                        "'Degradation ladder')")
    p.add_argument("--profile-dir",
                   help="capture a jax.profiler device trace here")
    p.set_defaults(fn=cmd_infer)

    p = sub.add_parser(
        "router",
        help="multi-replica front door: load-aware gRPC router over an "
             "engine replica pool (power-of-two-choices placement, "
             "session affinity, failover, rolling restarts — "
             "docs/SCALING.md)")
    p.add_argument("--port", type=int, default=0,
                   help="gRPC port the router serves LayerService on "
                        "(0 = ephemeral, printed as a JSON line)")
    p.add_argument("--replicas",
                   help="comma/space-separated host:port gRPC targets "
                        "of the engine replicas (the static fleet)")
    p.add_argument("--replica-metrics",
                   help="comma/space-separated host:port METRICS "
                        "endpoints, parallel to --replicas: enables "
                        "gauge-based p2c load (tdn_batcher_pending_rows "
                        "/ tdn_gen_slot_occupancy_ratio) and the "
                        "healthz drain choreography; without it the "
                        "router places by least-outstanding-requests")
    p.add_argument("--spawn", type=int, default=0, metavar="N",
                   help="spawn N local engine replicas as subprocesses "
                        "(tdn --platform <this one's> up --grpc-port 0 "
                        "--metrics-port 0 each; needs --config) and "
                        "manage their lifecycle, including "
                        "--drain-replica rolling restarts. Replicas are "
                        "not assigned chips: more than one on a TPU host "
                        "is refused (use --platform cpu, or --replicas)")
    p.add_argument("--config", help="model JSON the --spawn replicas serve")
    p.add_argument("--spawn-warm-rows", type=int, default=64,
                   help="bucket warm for spawned replicas (their "
                        "--serve-warm-rows; default 64)")
    p.add_argument("--scrape-interval", type=float, default=1.0,
                   help="seconds between replica /metrics + /healthz "
                        "load scrapes (default 1.0)")
    p.add_argument("--load-staleness", type=float, default=5.0,
                   help="gauge load older than this many seconds is "
                        "ignored and placement falls back to least-"
                        "outstanding-requests (default 5.0)")
    p.add_argument("--replica-weights", metavar="W[,W...]",
                   help="relative capacity weights, parallel to "
                        "--replicas (e.g. 4,1 for a TPU replica + CPU "
                        "spillover): the p2c load score divides by the "
                        "weight so heterogeneous replicas mix without "
                        "starving the fast one; without it weights "
                        "derive from each replica's scraped "
                        "tdn_engine_warm_buckets ladder, else 1")
    p.add_argument("--autoscale-min", type=int, default=None, metavar="N",
                   help="arm the fleet autopilot: never shrink below N "
                        "replicas (pass with --autoscale-max; needs "
                        "--metrics-port — the control loop runs on the "
                        "runtime sampler tick and reads the SLO burn "
                        "rate + scraped occupancy/pending gauges; "
                        "scale-up spawns local replicas via --config, "
                        "scale-down drains + removes through the "
                        "observed-drain choreography; docs/SCALING.md "
                        "'Autopilot')")
    p.add_argument("--autoscale-max", type=int, default=None, metavar="N",
                   help="autopilot upper bound: never grow past N "
                        "replicas")
    p.add_argument("--autoscale-target-occupancy", type=float,
                   default=0.6, metavar="F",
                   help="utilization the autopilot holds the fleet at "
                        "(default 0.6); scale-up past F*(1+hysteresis) "
                        "or on SLO fast burn > 1, scale-down below "
                        "F*(1-hysteresis)")
    p.add_argument("--hedge-after-p99-ratio", type=float, default=None,
                   metavar="R",
                   help="arm tail-latency request hedging for Process: "
                        "a forward outstanding longer than R x the "
                        "router's own measured p99 fires ONE second "
                        "attempt at another replica; first reply wins, "
                        "the loser is cancelled (try 2-3; "
                        "docs/SCALING.md 'Request hedging')")
    p.add_argument("--hedge-generate", action="store_true",
                   help="opt Generate into hedging too (OFF by "
                        "default: sampling is not idempotent — a "
                        "hedged Generate at temperature > 0 computes "
                        "different tokens on each replica and burns "
                        "decode slots on both)")
    p.add_argument("--serve-seconds", type=float, default=None,
                   help="serve for N seconds then drain and exit "
                        "(default: until interrupted)")
    p.add_argument("--drain-grace-seconds", type=float, default=5.0,
                   help="graceful-drain window for the ROUTER itself "
                        "on SIGTERM (in-flight forwards finish)")
    p.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                   help="expose /metrics + /healthz + the /router/* "
                        "admin routes (replica list, drain, undrain) "
                        "on this port (0 = ephemeral, printed as a "
                        "JSON line)")
    p.add_argument("--trace-sample-rate", type=float, default=None,
                   metavar="RATE",
                   help="head-sampling rate for router request tracing "
                        "in [0, 1]")
    _add_slo_args(p)
    _add_incident_args(p)
    p.add_argument("--admin", metavar="HOST:PORT",
                   help="admin-client mode: a RUNNING router's metrics "
                        "endpoint to drive (--drain-replica / "
                        "--undrain-replica / --list-replicas)")
    p.add_argument("--drain-replica", metavar="TARGET",
                   help="with --admin: stop placing on TARGET and let "
                        "it drain (the zero-downtime rolling-restart "
                        "step; pool-spawned replicas are also "
                        "SIGTERMed and respawned on the same address)")
    p.add_argument("--undrain-replica", metavar="TARGET",
                   help="with --admin: re-admit a drained replica "
                        "(fresh circuit breaker on the reused address)")
    p.add_argument("--quarantine-replica", metavar="TARGET",
                   help="with --admin: pull TARGET out of placement as "
                        "integrity-suspect (reason 'operator'; "
                        "docs/ROBUSTNESS.md 'Silent corruption & "
                        "quarantine')")
    p.add_argument("--unquarantine-replica", metavar="TARGET",
                   help="with --admin: re-admit a quarantined replica "
                        "— only passes after the fleet-fingerprint and "
                        "canary reverify succeed (see --force)")
    p.add_argument("--force", action="store_true",
                   help="with --unquarantine-replica: skip the "
                        "fingerprint + canary reverify (operator "
                        "override)")
    p.add_argument("--canary-interval", type=float, default=None,
                   metavar="SECONDS",
                   help="arm canary probing: every SECONDS per replica "
                        "the scrape loop sends a fixed seeded input "
                        "and exact-matches the reply against the "
                        "fleet's golden answer; an off-golden replica "
                        "is quarantined (needs --canary-dim or "
                        "--config for the input width)")
    p.add_argument("--canary-dim", type=int, default=None, metavar="D",
                   help="the canary Process input width (defaults to "
                        "the --config model's input dim)")
    p.add_argument("--spotcheck-rate", type=float, default=None,
                   metavar="F",
                   help="arm shadow spot-checks: duplicate this "
                        "fraction of Process traffic (e.g. 0.02) to a "
                        "second replica off the request path and "
                        "compare reply bytes; disagreement is "
                        "arbitrated by canary-probing both replicas")
    p.add_argument("--list-replicas", action="store_true",
                   help="with --admin: print the fleet snapshot JSON")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="admin-mode HTTP timeout in seconds (default 5)")
    p.set_defaults(fn=cmd_router)

    p = sub.add_parser(
        "fleet",
        help="fleet lifecycle tooling: `tdn fleet manifest` emits "
             "docker-compose/k8s specs wired for the drain/rejoin "
             "choreography (healthz probes, drain grace, stable "
             "replica addresses — docs/SCALING.md)")
    p.add_argument("action", choices=["manifest"],
                   help="manifest = emit an orchestrator spec for a "
                        "replica fleet + router")
    p.add_argument("--format", choices=["compose", "k8s"],
                   default="compose",
                   help="docker-compose (default) or k8s "
                        "(StatefulSet + headless Service for stable "
                        "replica DNS)")
    p.add_argument("--replicas-count", type=int, default=None,
                   metavar="N", help="fleet size to emit")
    p.add_argument("--admin", metavar="HOST:PORT",
                   help="size the manifest from a RUNNING router's "
                        "fleet instead (/router/replicas on its "
                        "metrics endpoint)")
    p.add_argument("--config", default="model.json",
                   help="model JSON the replicas serve (mounted "
                        "read-only; default model.json)")
    p.add_argument("--image", default="tpu-dist-nn:latest",
                   help="container image for every service "
                        "(default tpu-dist-nn:latest)")
    p.add_argument("--grpc-base-port", type=int, default=5101)
    p.add_argument("--metrics-base-port", type=int, default=9101)
    p.add_argument("--router-port", type=int, default=5100)
    p.add_argument("--router-metrics-port", type=int, default=9100)
    p.add_argument("--drain-grace-seconds", type=float, default=10.0,
                   help="replica drain window; the manifest's stop "
                        "grace / terminationGracePeriodSeconds covers "
                        "it (default 10)")
    p.add_argument("--spawn-warm-rows", type=int, default=64,
                   help="replica --serve-warm-rows (default 64)")
    p.add_argument("--autoscale-min", type=int, default=None,
                   help="include autopilot flags on the emitted "
                        "router command (with --autoscale-max)")
    p.add_argument("--autoscale-max", type=int, default=None)
    p.add_argument("--autoscale-target-occupancy", type=float,
                   default=0.6)
    p.add_argument("--hedge-after-p99-ratio", type=float, default=None,
                   help="include request hedging on the emitted "
                        "router command")
    p.add_argument("-o", "--out", default=None,
                   help="write the manifest here instead of stdout")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="--admin HTTP timeout in seconds (default 5)")
    p.set_defaults(fn=cmd_fleet)

    p = sub.add_parser("import-torch",
                       help="torch state dict (.pt) -> model JSON")
    p.add_argument("--state-dict", required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--activations",
                   help="comma list, one per dense layer "
                        "(default: relu...softmax, the reference tagging)")
    p.set_defaults(fn=cmd_import_torch)

    p = sub.add_parser("import-keras",
                       help="saved Keras model (.keras/.h5) -> model JSON")
    p.add_argument("--model", required=True,
                   help="path to a .keras (Keras 3) or legacy .h5 file")
    p.add_argument("--out", required=True)
    p.add_argument("--activations",
                   help="comma list overriding the model's own per-layer "
                        "activations")
    p.set_defaults(fn=cmd_import_keras)

    p = sub.add_parser("train", help="native on-TPU training")
    _add_multihost_args(p)
    p.add_argument("--config", help="start from an existing model JSON")
    p.add_argument("--layers", default=None,
                   help="fresh model sizes; default 784,128,64,10 "
                        "(generate_mnist_pytorch.py:25-27), or 64,32,16,10 "
                        "with --data digits")
    p.add_argument("--data", default="synthetic",
                   help="synthetic | fashion | digits (vendored real "
                        "handwritten digits) | idx:DIR | json:FILE")
    p.add_argument("--num-examples", type=int, default=12000)
    p.add_argument("--distribution")
    p.add_argument("--data-parallel", type=int, default=1)
    p.add_argument("--microbatches", type=int, default=4)
    p.add_argument("--schedule", choices=["gpipe", "1f1b", "interleaved"],
                   default="gpipe",
                   help="pipeline training schedule: gpipe (AD through the "
                        "forward schedule), 1f1b (activation-recompute, "
                        "O(stages) live memory), or interleaved "
                        "(auto-selected by --virtual-stages placements); "
                        "zero-bubble ('zb') is LM-only (tdn lm)")
    p.add_argument("--virtual-stages", type=int, default=1,
                   help="interleaved (Megatron virtual-stage) placement: "
                        "the distribution's V entries become V chunks on "
                        "V/v devices, trained by the table-driven schedule")
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--clip-norm", type=float, default=None,
                   help="global-norm gradient clipping")
    p.add_argument("--warmup-steps", type=int, default=0)
    p.add_argument("--lr-schedule", choices=["constant", "cosine"],
                   default="constant")
    p.add_argument("--weight-decay", type=float, default=0.0,
                   help="decoupled (AdamW) weight decay")
    p.add_argument("--grad-accum", type=int, default=1,
                   help="average gradients over N micro-steps per "
                        "optimizer update (N x effective batch at one "
                        "micro-batch's memory)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", help="export trained model JSON here")
    p.add_argument("--metrics-out",
                   help="write per-epoch training records as JSONL here")
    p.add_argument("--checkpoint-dir",
                   help="save per-epoch training state here and resume from it")
    p.add_argument("--keep-checkpoints", type=int, default=3)
    p.add_argument("--async-checkpoints", action="store_true",
                   help="write checkpoints on a background thread "
                        "(the step loop never blocks on disk)")
    p.add_argument("--checkpoint-format", choices=["native", "orbax"],
                   default="native",
                   help="native msgpack store or the Orbax ecosystem "
                        "format")
    p.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                   help="expose /metrics + /healthz for the duration of "
                        "the training run (0 = ephemeral, printed as a "
                        "JSON line)")
    p.add_argument("--trace-sample-rate", type=float, default=None,
                   metavar="RATE",
                   help="head-sampling rate for the run trace "
                        "(epoch spans on /trace) in [0, 1]")
    p.set_defaults(fn=cmd_train)

    p = sub.add_parser("lm", help="train + eval the Tiny-Transformer LM")
    _add_multihost_args(p)
    p.add_argument("--corpus", help="path to a text corpus (WikiText-2); "
                   "falls back to the synthetic corpus")
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--clip-norm", type=float, default=None,
                   help="global-norm gradient clipping")
    p.add_argument("--warmup-steps", type=int, default=0)
    p.add_argument("--lr-schedule", choices=["constant", "cosine"],
                   default="constant")
    p.add_argument("--weight-decay", type=float, default=0.0,
                   help="decoupled (AdamW) weight decay")
    p.add_argument("--grad-accum", type=int, default=1,
                   help="average gradients over N micro-steps per "
                        "optimizer update (N x effective batch at one "
                        "micro-batch's memory)")
    p.add_argument("--steps-per-call", type=int, default=1,
                   help="K optimizer steps per device call (one "
                        "lax.scan over a K-step superbatch): removes "
                        "per-step Python dispatch + host sync on the "
                        "single-chip path; losses fetch once per call")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--stages", type=int, default=1,
                   help="pipeline stages (per-block GPipe) when > 1")
    p.add_argument("--schedule",
                   choices=["gpipe", "1f1b", "interleaved", "zb", "zb-v",
                            "zb-stash"],
                   default="gpipe",
                   help="pipeline training schedule when --stages > 1 "
                        "(interleaved = Megatron virtual stages, see "
                        "--virtual-stages; zb = zero-bubble ZB-H1 split "
                        "backward, half the 1F1B bubble; zb-v = zero "
                        "bubble on the V-shape placement — bubble S-1 "
                        "chunk-ticks independent of M (zb needs larger "
                        "M to match), embedding+loss co-located; "
                        "zb-stash = ZB-H1 with the cotangent-stash "
                        "split: W ticks are pure dW GEMMs, no "
                        "recompute — the measured-cost zero bubble, "
                        "dense LM only, ~16x bridge memory)")
    p.add_argument("--virtual-stages", type=int, default=None,
                   help="model chunks per device for --schedule "
                        "interleaved/zb (bubble shrinks ~v-fold under "
                        "interleaved); default 2 for interleaved, 1 "
                        "(classic contiguous placement) for zb")
    p.add_argument("--data-parallel", type=int, default=1)
    p.add_argument("--seq-parallel", type=int, default=1,
                   help="shard the sequence axis over N devices "
                        "for long-context training (see --sp-mode)")
    p.add_argument("--tensor-parallel", type=int, default=1,
                   help="Megatron-shard each stage's blocks over N "
                        "devices (requires --stages > 1; composes with "
                        "--seq-parallel on every --schedule — the full "
                        "PP x TP x SP x DP deployment shape)")
    p.add_argument("--sample-tensor-parallel", type=int, default=1,
                   help="decode --sample-bytes with heads + KV cache "
                        "Megatron-sharded over N devices")
    p.add_argument("--sample-pipeline-stages", type=int, default=1,
                   help="decode --sample-bytes IN the pipeline "
                        "placement: blocks + per-stage KV caches over "
                        "N stage devices (greedy)")
    p.add_argument("--sp-mode", choices=["ring", "ulysses"], default="ring",
                   help="sequence-parallel decomposition: ring attention "
                        "(K/V rotation, O(T/N) memory) or ulysses "
                        "(all-to-all head scatter; needs heads %% N == 0)")
    p.add_argument("--microbatches", type=int, default=4)
    p.add_argument("--bf16", action="store_true",
                   help="bfloat16 compute (f32 master params + CE)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize block activations in the backward "
                        "(jax.checkpoint per block: long-context memory "
                        "for ~1/3 more FLOPs)")
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1: shard Adam moments over the data axis "
                        "(with --data-parallel N; dense LM)")
    p.add_argument("--fsdp", action="store_true",
                   help="fully-sharded (ZeRO-3): shard params AND Adam "
                        "moments over the data axis (dense LM)")
    p.add_argument("--experts", type=int, default=0,
                   help="MoE: experts per block (0 = dense MLP)")
    p.add_argument("--capacity-factor", type=float, default=1.25)
    p.add_argument("--router-top-k", type=int, default=1, choices=[1, 2],
                   help="experts per token: 1 = Switch, 2 = GShard gates")
    p.add_argument("--expert-parallel", type=int, default=1,
                   help="shard experts over this many devices (all_to_all)")
    p.add_argument("--checkpoint-dir",
                   help="save per-interval training state here and resume")
    p.add_argument("--keep-checkpoints", type=int, default=3)
    p.add_argument("--async-checkpoints", action="store_true",
                   help="write checkpoints on a background thread "
                        "(the step loop never blocks on disk)")
    p.add_argument("--checkpoint-format", choices=["native", "orbax"],
                   default="native",
                   help="native msgpack store or the Orbax ecosystem "
                        "format")
    p.add_argument("--metrics-out",
                   help="write per-step training records + the final "
                        "eval report as JSONL here")
    p.add_argument("--log-every", type=int, default=50,
                   help="record loss every N steps (each record "
                        "fetches its loss, so its timestamp covers "
                        "finished device work)")
    p.add_argument("--eval-batches", type=int, default=0,
                   help="cap the held-out eval at N batches (default 0 "
                        "= the full split, comparable across rounds; "
                        "a truncating cap logs a warning — the 8 MB "
                        "corpus can mean thousands of eval batches at "
                        "small seq). The report records eval_rows_used")
    p.add_argument("--profile-dir",
                   help="capture a jax.profiler device trace of the "
                        "training loop here")
    p.add_argument("--sample-bytes", type=int, default=0,
                   help="generate this many bytes after training")
    p.add_argument("--prompt", default="The ", help="generation prompt")
    p.add_argument("--top-k", type=int, default=None,
                   help="sample from the k highest-probability bytes only")
    p.add_argument("--top-p", type=float, default=None,
                   help="nucleus sampling: smallest set with cumulative "
                        "probability >= p")
    p.add_argument("--temperature", type=float, default=0.8,
                   help="0 = greedy")
    p.add_argument("--model-config", default=None, metavar="FILE.json",
                   help="serve a published architecture instead of "
                        "training the Tiny-Transformer: a config.json in "
                        "the source's own keys (model_type: minicpm_sala, "
                        "phi4flash or kimi_k2), "
                        "seeded random weights in its param_dtype, served "
                        "by --serve-generate on the continuous scheduler "
                        "(docs/MODEL_CONFIG.md)")
    p.add_argument("--serve-generate", type=int, default=None,
                   metavar="PORT",
                   help="after training, serve GENERATION on this port "
                        "(0 = ephemeral; the reference wire's Matrix "
                        "of token ids on LayerService/Generate). "
                        "Sampling follows --temperature/--top-k/--top-p")
    p.add_argument("--serve-stages", type=int, default=1,
                   help="serve decode in the pipelined placement with "
                        "the OVERLAPPED round-robin decoder (requests "
                        "coalesce into its group slots)")
    p.add_argument("--serve-groups", type=int, default=None,
                   help="round-robin request groups for --serve-stages "
                        "(default max(stages, 2))")
    p.add_argument("--serve-prompt-len", type=int, default=16,
                   help="the endpoint's static prompt length")
    p.add_argument("--serve-new-tokens", type=int, default=32,
                   help="tokens generated per request")
    p.add_argument("--scheduler", choices=["auto", "static", "continuous"],
                   default="auto",
                   help="decode scheduling for --serve-generate: "
                        "continuous = iteration-level slot scheduler "
                        "(admit at step granularity, retire on EOS/"
                        "budget; docs/PERF.md 'Continuous batching'); "
                        "static = the legacy run-to-completion batch "
                        "(the A/B control arm); auto (default) = "
                        "continuous single-chip, static pipelined")
    p.add_argument("--gen-slots", type=int, default=8,
                   help="KV-cache slots of the continuous scheduler "
                        "(concurrent sequences decoding per step; "
                        "tuning guide in docs/PERF.md)")
    p.add_argument("--eos-id", type=int, default=None,
                   help="stop token: a generated row freezes at this "
                        "byte id and pads the remainder with it "
                        "(applies to --sample-bytes, and to both "
                        "--serve-generate schedulers identically)")
    p.add_argument("--prefix-cache-blocks", type=int, default=0,
                   help="reserve this many shared-prefix KV pool "
                        "blocks in the continuous scheduler's slot "
                        "cache: requests whose prompts share a cached "
                        "prefix admit by block copy + suffix-only "
                        "prefill (ref-counted, LRU-evicted; "
                        "docs/PERF.md 'Prefix caching & chunked "
                        "prefill'; 0 = off)")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   metavar="TOKENS",
                   help="split prompt prefills into chunks of at most "
                        "this many tokens, one chunk per scheduler "
                        "iteration, so a long prompt stops stalling "
                        "resident decode streams; also the prefix-"
                        "cache tier granularity (default: whole "
                        "prompt in one launch)")
    p.add_argument("--serve-seconds", type=float, default=None,
                   help="serve for N seconds then exit (default: until "
                        "interrupted)")
    p.add_argument("--stream", action="store_true",
                   help="client-only streaming demo: connect to a "
                        "running --serve-generate endpoint (--target "
                        "HOST:PORT; router front doors work too) and "
                        "stream ONE generation of --prompt over "
                        "LayerService/GenerateStream, printing bytes "
                        "as each token frame lands (first output at "
                        "~TTFT, not retirement) plus a JSON latency "
                        "summary. Prompt pads/truncates to "
                        "--serve-prompt-len")
    p.add_argument("--target", default=None, metavar="HOST:PORT",
                   help="the --serve-generate endpoint for --stream")
    p.add_argument("--session-key", default=None,
                   help="x-tdn-session affinity key for --stream "
                        "behind a router")
    p.add_argument("--max-pending-rows", type=int, default=None,
                   help="admission-control watermark for --serve-generate: "
                        "requests that would queue past this many pending "
                        "rows are shed with RESOURCE_EXHAUSTED (default: "
                        "unbounded; docs/ROBUSTNESS.md)")
    p.add_argument("--class-watermarks", default=None, metavar="SPEC",
                   help="per-SLO-class shed fractions of "
                        "--max-pending-rows for --serve-generate, e.g. "
                        "'critical=1.0,standard=1.0,best_effort=0.5' "
                        "(the default; docs/ROBUSTNESS.md "
                        "'Degradation ladder')")
    p.add_argument("--drain-grace-seconds", type=float, default=5.0,
                   help="graceful-drain window on SIGTERM while serving "
                        "(--serve-generate): finish in-flight decodes "
                        "within this long before exit")
    p.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                   help="expose /metrics + /healthz for the run — "
                        "training counters during the loop, serving "
                        "counters under --serve-generate (0 = "
                        "ephemeral, printed as a JSON line)")
    p.add_argument("--trace-sample-rate", type=float, default=None,
                   metavar="RATE",
                   help="head-sampling rate for request-scoped tracing "
                        "in [0, 1] (log-interval spans during the "
                        "loop, per-request spans under "
                        "--serve-generate)")
    _add_slo_args(p)
    _add_incident_args(p)
    p.set_defaults(fn=cmd_lm)

    p = sub.add_parser("doctor",
                       help="environment self-check (backend, devices, "
                            "native lib, kernels, oracle parity)")
    p.add_argument("--serving", action="store_true",
                   help="also run a loopback gRPC serving round trip "
                        "(server + client through the real wire codec)")
    p.add_argument("--multichip", type=int, metavar="N", default=None,
                   help="also run the driver's N-device multi-chip dry "
                        "run (virtual CPU mesh, subprocess) under "
                        "--multichip-budget; unhealthy if it fails or "
                        "exceeds the budget")
    p.add_argument("--multichip-budget", type=float, default=300.0,
                   metavar="SECONDS",
                   help="time budget for --multichip (default 300)")
    p.set_defaults(fn=cmd_doctor)

    p = sub.add_parser("oracle", help="numpy float64 baseline (manual_nn)")
    p.add_argument("--config", required=True)
    p.add_argument("--inputs", required=True)
    p.set_defaults(fn=cmd_oracle)

    p = sub.add_parser("warmup",
                       help="precompile the serving pow2 bucket ladder "
                            "— or, with --lm, the continuous-batching "
                            "generation kernels — (no port opened; "
                            "pairs with JAX_COMPILATION_CACHE_DIR to "
                            "pre-warm across processes)")
    _add_up_args(p, config_required=False)
    _add_multihost_args(p)
    p.add_argument("--rows", type=int, default=64,
                   help="warm every power-of-two bucket up to this many "
                        "rows (default 64, matching --serve-warm-rows)")
    p.add_argument("--lm", action="store_true",
                   help="warm the LM generation path instead of the "
                        "engine ladder: the continuous scheduler's "
                        "prefill-at-slot + slot-step kernels for the "
                        "shape flags below")
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--gen-slots", type=int, default=8,
                   help="decode slots of the server being warmed")
    p.add_argument("--serve-prompt-len", type=int, default=16)
    p.add_argument("--serve-new-tokens", type=int, default=32)
    p.add_argument("--prefix-cache-blocks", type=int, default=0,
                   help="match the server's --prefix-cache-blocks so "
                        "the slot-copy kernel (and the suffix chunk "
                        "lengths a prefix hit produces) precompile too")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   metavar="TOKENS",
                   help="match the server's --prefill-chunk so every "
                        "chunk length precompiles")
    p.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                   help="expose /metrics during the warm (0 = ephemeral, "
                        "printed as a JSON line) — the "
                        "tdn_engine_warm_buckets gauge tracks progress")
    p.set_defaults(fn=cmd_warmup)

    p = sub.add_parser("metrics",
                       help="one-shot scrape of a --metrics-port "
                            "endpoint (pretty-printed or --raw)")
    p.add_argument("--target", required=True,
                   help="host:port of a running --metrics-port endpoint")
    p.add_argument("--raw", action="store_true",
                   help="dump the Prometheus text exposition as-is")
    p.add_argument("--aggregate", action="store_true",
                   help="against a ROUTER endpoint: scrape the router "
                        "AND every pool replica in one shot (fleet "
                        "discovery via /router/replicas; counters "
                        "summed, gauges per replica)")
    p.add_argument("--profile", action="store_true",
                   help="with --aggregate: fan /profile out over the "
                        "fleet and merge per-stage self time across "
                        "replicas (router.forward lane included) — "
                        "'where does fleet time go' as one table "
                        "(--raw dumps the merged JSON)")
    p.add_argument("--timeseries", default=None, metavar="FAMILY",
                   help="with --aggregate: also fan /timeseries out "
                        "over the fleet for FAMILY and dump the "
                        "merged per-source series as JSON")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="HTTP timeout in seconds (default 5)")
    p.set_defaults(fn=cmd_metrics)

    p = sub.add_parser("trace",
                       help="pull recorded request spans from a "
                            "--metrics-port endpoint as a Chrome "
                            "trace-event file (Perfetto-loadable)")
    p.add_argument("--target", required=True,
                   help="host:port of a running --metrics-port endpoint")
    p.add_argument("-o", "--out", default="trace.json",
                   help="output path (default trace.json); open in "
                        "https://ui.perfetto.dev or chrome://tracing")
    p.add_argument("--limit", type=int, default=None,
                   help="at most N most-recent ring-buffer spans "
                        "(slowest-trace exemplars always included)")
    p.add_argument("--aggregate", action="store_true",
                   help="against a ROUTER endpoint: pull /trace from "
                        "the router AND every replica (discovery via "
                        "/router/replicas) and STITCH them into one "
                        "Chrome trace — spans joined by trace id, one "
                        "lane per process")
    p.add_argument("--trace-id", default=None, metavar="ID",
                   help="pull only this trace (the id a log line, "
                        "x-tdn-trace-id trailer, or /slo exemplar "
                        "named) instead of the whole ring")
    p.add_argument("--since", type=int, default=None, metavar="CURSOR",
                   help="incremental pull: only spans that finished "
                        "after this cursor (the 'cursor' value the "
                        "previous pull printed) — pollers stop "
                        "re-downloading the whole ring every tick")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="HTTP timeout in seconds (default 5)")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser(
        "top",
        help="live fleet dashboard over a --metrics-port endpoint "
             "(router: every replica; rps, p50/p99, slots, pending, "
             "breaker state, prefix hit ratio, SLO budget, sparklines)")
    p.add_argument("--target", required=True,
                   help="host:port of a running --metrics-port "
                        "endpoint (a router's for the fleet view)")
    p.add_argument("--interval", type=float, default=2.0,
                   help="seconds between polls (default 2)")
    p.add_argument("--iterations", type=int, default=None, metavar="N",
                   help="render N frames then exit (default: run until "
                        "Ctrl-C; the CI/smoke bound)")
    p.add_argument("--no-color", action="store_true",
                   help="plain frames without ANSI escapes (also the "
                        "non-TTY default)")
    p.add_argument("--timeout", type=float, default=3.0,
                   help="per-request HTTP timeout in seconds "
                        "(default 3)")
    p.set_defaults(fn=cmd_top)

    p = sub.add_parser("profile",
                       help="pull a --metrics-port endpoint's per-stage "
                            "self-time breakdown (the 'where does the "
                            "time go' table), optionally with an "
                            "on-demand device-trace capture")
    p.add_argument("--target", required=True,
                   help="host:port of a running --metrics-port endpoint")
    p.add_argument("--window", type=float, default=None,
                   help="only traces whose root ended within the last "
                        "N seconds (default: everything buffered)")
    p.add_argument("--top", type=int, default=5,
                   help="slowest exemplar traces per method (default 5)")
    p.add_argument("--json", action="store_true",
                   help="dump the raw /profile JSON instead of the table")
    p.add_argument("--capture-seconds", type=float, default=None,
                   metavar="N",
                   help="also capture a jax.profiler device trace for N "
                        "seconds via /debug/profile (503s gracefully on "
                        "backends without profiler support)")
    p.add_argument("--capture-out", default="device_profile.zip",
                   help="where the capture zip lands (default "
                        "device_profile.zip)")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="HTTP timeout in seconds (default 5)")
    p.set_defaults(fn=cmd_profile)

    p = sub.add_parser(
        "incident",
        help="browse a serving endpoint's flight-recorder store: "
             "anomaly/crash-triggered diagnostic bundles "
             "(docs/OBSERVABILITY.md 'Incidents & flight recorder')")
    p.add_argument("action", choices=["ls", "show", "pull"],
                   help="ls = list captured bundles; show ID = print "
                        "one manifest; pull ID = download the zip")
    p.add_argument("id", nargs="?", default=None,
                   help="incident id (from `tdn incident ls`)")
    p.add_argument("--target", required=True,
                   help="host:port of a running --metrics-port "
                        "endpoint started with --incident-dir")
    p.add_argument("-o", "--out", default=None,
                   help="pull: output path (default <id>.zip)")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="HTTP timeout in seconds (default 5)")
    p.set_defaults(fn=cmd_incident)

    p = sub.add_parser(
        "debug",
        help="on-demand diagnostic capture from a running endpoint "
             "(tdn debug bundle --target ...; a router captures the "
             "whole fleet and stitches the trace)")
    p.add_argument("what", choices=["bundle"],
                   help="bundle = GET /debug/bundle and save the zip")
    p.add_argument("--target", required=True,
                   help="host:port of a running --metrics-port "
                        "endpoint (a router's for fleet capture)")
    p.add_argument("-o", "--out", default="bundle.zip",
                   help="output path (default bundle.zip)")
    p.add_argument("--reason", default=None,
                   help="free-text reason recorded in the manifest")
    p.add_argument("--no-fleet", action="store_true",
                   help="against a router: capture the router process "
                        "only, skip the replica fan-out")
    p.add_argument("--timeout", type=float, default=10.0,
                   help="HTTP timeout in seconds (default 10; the "
                        "request itself gets +30s for the capture)")
    p.set_defaults(fn=cmd_debug)

    p = sub.add_parser(
        "lint",
        help="machine-checked project invariants (tools/tdnlint): "
             "lock discipline, tick purity, metric-series lifecycle, "
             "admin actuation, jit purity — exit 1 on any "
             "non-baselined finding (docs/STATIC_ANALYSIS.md)",
    )
    p.add_argument("paths", nargs="*",
                   help="files/packages to scan (default: the "
                        "tpu_dist_nn package)")
    p.add_argument("--rule", action="append", metavar="RULE",
                   help="run only this rule (repeatable)")
    p.add_argument("--baseline", default=None,
                   help="baseline JSON (default tools/tdnlint/"
                        "baseline.json; pass '' to disable)")
    p.add_argument("--update-baseline", action="store_true",
                   help="rewrite the baseline to the current finding "
                        "set (keeps existing justifications)")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule ids and exit")
    p.add_argument("--json", dest="lint_json", action="store_true",
                   help="also print one machine-readable JSON line")
    p.set_defaults(fn=cmd_lint)

    p = sub.add_parser(
        "replay",
        help="scenario engine: trace-driven workload capture & "
             "replay crossed with the chaos-load matrix — run "
             "declarative scenarios on a loopback fleet with real "
             "SLO verdicts, or fire a captured bundle / saved trace "
             "at a live target (docs/OBSERVABILITY.md 'Capture & "
             "replay')")
    p.add_argument("--scenario", default=None,
                   help="one scenario spec JSON to run (exit 0 pass, "
                        "2 fail)")
    p.add_argument("--scenario-dir", default=None,
                   help="run every *.json scenario in a directory "
                        "(the checked-in matrix lives in scenarios/)")
    p.add_argument("--bundle", default=None,
                   help="incident bundle zip: extract its "
                        "WorkloadTrace and replay it at --target")
    p.add_argument("--trace", default=None,
                   help="saved WorkloadTrace JSON to replay at "
                        "--target")
    p.add_argument("--generate", default=None,
                   metavar="GENERATOR",
                   help="emit a seeded synthetic WorkloadTrace "
                        "(diurnal, flash_crowd, heavy_tail, "
                        "shared_prefix_flood, mixed_class) instead "
                        "of replaying")
    p.add_argument("--generator-args", default=None,
                   help="JSON kwargs for --generate (e.g. "
                        "'{\"requests\": 200, \"duration\": 60}')")
    p.add_argument("--target", default=None,
                   help="host:port to replay against (--bundle/"
                        "--trace mode). With --scenario/"
                        "--scenario-dir: remote load-test mode — "
                        "fire the scenario's workload at the live "
                        "fleet with fault injection, chaos events, "
                        "and SLO scoring disabled (they are "
                        "loopback-only); the report carries a "
                        "caveat and no pass/fail verdict")
    p.add_argument("--speed", type=float, default=None,
                   help="arrival-process multiplier (2 = twice as "
                        "fast; default 1, or the scenario's own)")
    p.add_argument("--seed", type=int, default=None,
                   help="override the scenario/generator seed")
    p.add_argument("--quick-scale", type=float, default=None,
                   help="shrink scenario workloads by this factor "
                        "(rates preserved) — the CI smoke setting")
    p.add_argument("--dim", type=int, default=8,
                   help="Process row width when the trace does not "
                        "record one (default 8)")
    p.add_argument("--prompt-len", type=int, default=8,
                   help="target endpoint's static prompt length for "
                        "Generate replay (default 8)")
    p.add_argument("--vocab-size", type=int, default=64,
                   help="token id range for synthesized prompts "
                        "(default 64)")
    p.add_argument("--timeout", type=float, default=30.0,
                   help="per-request client timeout seconds "
                        "(default 30)")
    p.add_argument("-o", "--out", default=None,
                   help="write the verdict/report/trace JSON here "
                        "instead of stdout")
    p.add_argument("--pretty", action="store_true",
                   help="indent the JSON output")
    p.set_defaults(fn=cmd_replay)

    return parser


def _uses_backend(args) -> bool:
    """Whether this invocation runs anything on a device.

    The commands that registered the multihost args (up/infer/train/
    lm/warmup) and ``doctor`` do. Their pure-client forms — ``infer
    --target``/``--port`` and ``lm --stream`` — only speak gRPC, and
    must not open a chip the server they talk to already owns."""
    if args.command == "doctor":
        return True
    if not hasattr(args, "coordinator"):
        return False
    if args.command == "infer":
        return not (args.target
                    or (args.port is not None and not args.config))
    if args.command == "lm":
        return not args.stream
    return True


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if getattr(args, "log_json", False):
        from tpu_dist_nn.obs.log import setup_json_logging

        setup_json_logging()
    try:
        if _uses_backend(args):
            import jax

            from tpu_dist_nn.utils import backend
            from tpu_dist_nn.utils.errors import UnavailableError

            # cpu pins the host before anything initializes; auto/tpu
            # leave the resolution to JAX. The join of a multi-process
            # job must precede backend init, the platform check needs
            # it — hence the order.
            if args.platform == "cpu":
                jax.config.update("jax_platforms", "cpu")
            cache_dir = backend.enable_compile_cache()
            _init_multihost(args)
            try:
                device = backend.require_platform(args.platform)
            except UnavailableError as e:
                print(f"error: --platform {args.platform}: {e}",
                      file=sys.stderr)
                return 3
            from tpu_dist_nn.obs.log import get_logger

            get_logger(log.name).info(
                "backend.resolved", requested=args.platform, **device,
                compile_cache_dir=cache_dir,
            )
        return args.fn(args)
    except (ValueError, FileNotFoundError) as e:
        # Config/placement errors are user errors, not crashes — the
        # analogue of the reference's fail-fast validation messages.
        print(f"error: {e}", file=sys.stderr)
        return 2
    finally:
        # Any --metrics-port endpoint a command's error path left
        # running must not outlive the command (in-process callers —
        # the tests — would hit the stale bound port on a rerun).
        _drain_metrics_servers()


if __name__ == "__main__":
    sys.exit(main())
