"""Shared benchmark runner — the L6 entry-point layer.

The reference implements twelve near-identical script bodies (parse → MPIComm
→ shape probe → model → runtime → dataset → epoch loop with CUDA-event img/s
timing; flagship flow `benchmark_amoebanet_sp.py:116-371`).  Here the flow is
one function parameterized by (family, model):

    parse flags (config.get_parser, reference parser.py vocabulary)
    → MeshSpec.from_config / build_mesh     (replaces MPIComm rank math)
    → build_model + spatial_until placement (replaces the two-phase shape
      probe: shapes come from jax.eval_shape inside the builders)
    → the family's train-step builder       (replaces train_model* runtimes)
    → make_dataset APP dispatch             (reference APP 1/2/3)
    → epoch loop printing per-step images/sec + mean/median via StepMeter
      (reference output format, benchmark_amoebanet_sp.py:322-367)

Families:
  lp       — LP/PP pipeline (reference benchmarks/layer_parallelism)
  sp       — spatial(+pipeline tail) (reference benchmarks/spatial_parallelism)
  gems     — GEMS bidirectional (reference benchmarks/gems_master_model)
  gems_sp  — GEMS x SP x PP (reference gems_master_with_spatial_parallelism)

Every script runs on any JAX platform; on a CPU host pass small flags, e.g.
  JAX_PLATFORMS=cpu python benchmark_resnet_sp.py --image-size 32 \
      --num-layers 1 --batch-size 4
Where ``JAX_PLATFORMS`` names the CPU the runner provisions the virtual CPU
devices its mesh needs; on any other platform a mesh larger than
``jax.devices()`` is an error, never a silent CPU run.
"""

from __future__ import annotations

import os
import sys

# Make `mpi4dl_tpu` importable when a benchmark script is run by path.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from mpi4dl_tpu.config import ParallelConfig, config_from_args, get_parser
from mpi4dl_tpu.utils import StepMeter


def _resolve_spatial_until(cfg: ParallelConfig, n_cells: int, shapes):
    """Resolve cfg.spatial_until to a concrete junction cell (or None when
    unset): an explicit int is clamped to the legal [1, n_cells-1] range;
    ``"auto"`` asks the analytical placement frontier
    (parallel/spatial.choose_spatial_until) — the ``mem_probe
    --sweep-junction`` chooser running as default config."""
    su = cfg.spatial_until
    if su is None:
        return None
    if su == "auto":
        import jax.numpy as jnp

        from mpi4dl_tpu.parallel.spatial import choose_spatial_until

        assert shapes is not None, "--spatial-until auto needs cell shapes"
        tiles = cfg.spatial_part_size
        itemsize = jnp.dtype(cfg.compute_dtype).itemsize
        su = choose_spatial_until(shapes, tiles, itemsize=itemsize)
        print(f"note: --spatial-until auto resolved to {su} "
              f"(analytical placement frontier, {tiles} tiles)")
    clamped = max(1, min(int(su), n_cells - 1))
    if clamped != int(su):
        # Placement is the dominant memory lever (PERF_NOTES: su=18 vs 22
        # is 123.9 vs 59.4 GB) — never re-place a pinned junction silently.
        print(f"note: --spatial-until {su} clamped to {clamped} "
              f"({n_cells}-cell model)")
    return clamped


def _spatial_levels(cfg: ParallelConfig, n_cells: int, shapes=None):
    """[(stop_cell, SpatialCtx)] for the spatial region.

    Level i covers the cells of pipeline split i (reference: the first
    `spatial_size` splits run conv_spatial, resnet_spatial.py:272-296) with
    `num_spatial_parts[i]` tiles (multi-level SP, train_spatial.py:453-504);
    a short parts list repeats its last element, and consecutive levels with
    identical grids merge (no respatial between them).  ``cfg.spatial_until``
    (int or "auto") overrides the junction placement derived from the
    splits."""
    from mpi4dl_tpu.cells import split_even
    from mpi4dl_tpu.layer_ctx import spatial_levels_for

    ranges = split_even(n_cells, max(cfg.split_size, 1), cfg.balance)
    k = min(max(cfg.spatial_size, 1), len(ranges))
    if cfg.split_size > 1 and k >= cfg.split_size:
        # The SPxPP engine needs a non-spatial pipeline tail (the reference's
        # models likewise keep non-spatial layers past end_layer — the head
        # cannot run tiled).  Clamp and say so.
        k = cfg.split_size - 1
        print(
            f"note: spatial_size clamped to {k} (split_size {cfg.split_size} "
            "needs at least one non-spatial tail split)"
        )
    parts = list(cfg.num_spatial_parts)
    if len(parts) > k:
        print(
            f"note: num_spatial_parts {parts} has more levels than the "
            f"{k} spatial split(s); using {parts[:k]} (raise --spatial-size "
            "and --split-size to use the full chain)"
        )
    parts = (parts + [parts[-1]] * k)[:k]
    ctxs = spatial_levels_for(
        cfg.slice_method,
        parts,
        bn_cross_tile=cfg.bn_cross_tile,
        d2_mode=cfg.halo_d2,
        # --fused-layers caps margin-consuming layers per fused exchange
        # (reference resnet_spatial_d2.py get_balance); <=0 → maximal fusion.
        d2_max_fused=cfg.fused_layers if cfg.fused_layers > 0 else None,
    )
    levels = []
    for i in range(k):
        # The head cell can never run tiled (its global pooling kernel
        # exceeds any tile), so the junction comes before it — same reason
        # apply_spatial_model's default spatial_until is len(cells)-1.
        stop = min(ranges[i][1], n_cells - 1)
        if levels and ctxs[i] == levels[-1][1]:
            levels[-1] = (stop, ctxs[i])
        elif stop > (levels[-1][0] if levels else 0):
            levels.append((stop, ctxs[i]))
    su = _resolve_spatial_until(cfg, n_cells, shapes)
    if su is not None:
        # Re-place the junction: clamp the level chain at the new stop
        # (dropping levels that now start past it) or extend the last level
        # to reach it — interior level boundaries keep their positions.
        clamped = []
        for stop, c in levels:
            prev = clamped[-1][0] if clamped else 0
            if prev >= su:
                break
            clamped.append((min(stop, su), c))
        clamped[-1] = (su, clamped[-1][1])
        levels = clamped
    return levels


def _check_token_family(cfg: ParallelConfig, family: str, dtype) -> None:
    """What a token model trains through: the one-chip step (with data
    parallelism) and the GPipe pipeline over its cells.  The spatial families
    shard an image's H and W and GEMS pairs mirrored image batches; a
    sequence axis as a sharded axis is not built yet (ROADMAP R6)."""
    import jax.numpy as jnp

    if family != "lp" or cfg.schedule != "gpipe":
        raise ValueError(
            f"--model {cfg.model} is a token model: it trains through the lp "
            f"family (one chip, or GPipe over cells with --split-size), not "
            f"through {family!r}"
            + (f" with --schedule {cfg.schedule}" if family == "lp" else "")
            + "; the sequence axis is not a sharded axis yet (ROADMAP R6)")
    if cfg.split_size > 1 and 2 ** (jnp.finfo(dtype).nmant + 1) < cfg.vocab_size:
        # the stage buffers are flat vectors in the compute dtype, and the
        # ids enter stage 0 through one
        raise ValueError(
            f"--split-size {cfg.split_size} carries the ids to stage 0 in the "
            f"compute dtype, and {jnp.dtype(dtype).name} holds only "
            f"{2 ** (jnp.finfo(dtype).nmant + 1)} of --vocab-size "
            f"{cfg.vocab_size} exactly: use --precision fp_32")


def build_train(cfg: ParallelConfig, family: str, mesh):
    """Return (step, state, eval_params_fn, global_batch).

    ``eval_params_fn(state) -> params_list`` reassembles full parameters for
    the eval step / checkpointing regardless of the family's state layout,
    one entry a cell as the cell is applied to it.

    Recorded as the ``setup/build_train`` span with ``setup/build_model``,
    ``setup/init_params``, ``setup/make_step`` and ``setup/place_state``
    inside it (obs/spans.py); jax's trace, lower and compile-or-load events
    that fall in it are its ``jax/*`` children.
    """
    from mpi4dl_tpu.obs.spans import recorder

    # One frame, as before the spans: jax's lowering slows with the depth of
    # the Python stack it is called from (PERF.md, PR 26), and model.init
    # lowers sixty programs from in here.
    rec = recorder()
    with rec.span("setup/build_train"):
        import jax

        from mpi4dl_tpu.models import build_model
        from mpi4dl_tpu.train import Optimizer, TrainState

        from mpi4dl_tpu.quant import QuantPolicy

        if cfg.stripe_bwd:
            # The stripe-wise backward is dispatched at trace time off the
            # MPI4DL_STRIPE_BWD hatch (like the other layer-dispatch hatches);
            # the config flag sets it for this process before any step builds.
            # Deliberately NOT cleared when cfg.stripe_bwd is false: tracing
            # happens after build_train returns, and the env-var hatch is a
            # documented interface of its own (HATCHES) — an in-process
            # striped-vs-plain A/B must manage the variable itself (as the
            # tests do via monkeypatch).
            os.environ["MPI4DL_STRIPE_BWD"] = "1"
        with rec.span("setup/build_model"):
            model = build_model(cfg)
        with rec.span("setup/init_params"):
            params, shapes = model.init(jax.random.key(cfg.seed))
        opt = Optimizer(cfg.optimizer, lr=cfg.lr, momentum=cfg.momentum)
        dp = cfg.data_parallel
        dtype = cfg.compute_dtype
        pdtype = cfg.param_dtype
        # Quantized-collective policy (None = off = bit-identical engines);
        # the MPI4DL_QUANT_COLLECTIVES hatch overrides the --quant flag.
        quant = QuantPolicy.resolve(cfg.quant_collectives)
        if quant is not None:
            print(f"note: quantized collectives on: {quant.spec()}",
                  file=sys.stderr)
        if cfg.precision == "bf_16_all":
            # bf_16_all: parameters stored bf16 as well (reference parser.py
            # precision vocabulary); fp32 update arithmetic lives in Optimizer.
            params = jax.tree.map(lambda p: p.astype(pdtype), params)
        from_probs = cfg.softmax_in_model

        if cfg.is_token_model:
            _check_token_family(cfg, family, dtype)

        if cfg.schedule != "gpipe" and cfg.split_size <= 1:
            print(
                f"note: --schedule {cfg.schedule} needs a pipeline "
                "(--split-size >= 2); single-chip path ignores it",
                file=sys.stderr,
            )

        if family == "lp":
            if cfg.split_size <= 1:
                from mpi4dl_tpu.train import make_train_step

                with rec.span("setup/make_step"):
                    step = make_train_step(
                        model, opt, mesh if dp > 1 else None, parts=cfg.parts,
                        compute_dtype=dtype, from_probs=from_probs,
                        remat=cfg.remat, donate=True,
                    )
                with rec.span("setup/place_state"):
                    state = TrainState.create(params, opt)
                # per cell: a tied leaf, which the state holds once, is
                # there for each cell that reads it (the same array)
                return (step, state, (lambda s: model.per_cell(s.params)),
                        cfg.batch_size * dp)
            from mpi4dl_tpu.parallel.partition import StagePartition
            from mpi4dl_tpu.parallel.pipeline import (
                init_pipeline_state,
                make_pipeline_train_step,
            )

            mb = cfg.batch_size // cfg.parts
            part = StagePartition.build(
                model, params, cfg.split_size,
                (mb, *cfg.sample_shape),
                balance=cfg.balance, compute_dtype=dtype, param_dtype=pdtype,
                # the GPipe schedule sums a tied leaf's gradients over stages
                sums_tied_grads=cfg.schedule == "gpipe",
            )
            with rec.span("setup/make_step"):
                step = make_pipeline_train_step(
                    part, opt, mesh, cfg.parts, compute_dtype=dtype,
                    remat=cfg.remat, from_probs=from_probs,
                    with_data_axis=dp > 1, donate=True, schedule=cfg.schedule,
                    quant=quant,
                )
            with rec.span("setup/place_state"):
                state = init_pipeline_state(part, params, opt, mesh)
            return (
                step, state,
                (lambda s: part.unpack_params(jax.device_get(s.param_buf))),
                cfg.batch_size * dp,
            )

        if family == "gems":
            from mpi4dl_tpu.parallel.gems import make_gems_train_step
            from mpi4dl_tpu.parallel.partition import StagePartition
            from mpi4dl_tpu.parallel.pipeline import init_pipeline_state

            groups = 2 * cfg.times * cfg.parts
            assert cfg.batch_size % groups == 0, (
                f"GEMS needs batch_size divisible by 2*times*parts={groups}"
            )
            mb = cfg.batch_size // groups
            part = StagePartition.build(
                model, params, cfg.split_size,
                (mb, *cfg.sample_shape),
                balance=cfg.balance, compute_dtype=dtype, param_dtype=pdtype,
            )
            with rec.span("setup/make_step"):
                step = make_gems_train_step(
                    part, opt, mesh, cfg.parts, times=cfg.times,
                    compute_dtype=dtype, remat=cfg.remat, from_probs=from_probs,
                    with_data_axis=dp > 1, donate=True, schedule=cfg.schedule,
                    quant=quant,
                )
            with rec.span("setup/place_state"):
                state = init_pipeline_state(part, params, opt, mesh)
            return (
                step, state,
                (lambda s: part.unpack_params(jax.device_get(s.param_buf))),
                cfg.batch_size * dp,
            )

        # Spatial families
        levels = _spatial_levels(cfg, len(model.cells), shapes=shapes)
        sp = levels[0][1]
        model.spatial_until = levels[-1][0]
        junction = "batch_split" if cfg.local_dp_lp > 1 else "gather"
        local_dp = cfg.local_dp_lp if cfg.local_dp_lp > 1 else None

        if family == "sp" and cfg.split_size <= 1:
            from mpi4dl_tpu.train import make_spatial_train_step

            with rec.span("setup/make_step"):
                step = make_spatial_train_step(
                    model, opt, mesh, sp, parts=cfg.parts, with_data_axis=dp > 1,
                    compute_dtype=dtype, from_probs=from_probs,
                    spatial_until=model.spatial_until, junction=junction,
                    levels=levels, local_dp=local_dp, donate=True, quant=quant,
                )
            with rec.span("setup/place_state"):
                state = TrainState.create(params, opt)
            return step, state, (lambda s: s.params), cfg.batch_size * dp

        from mpi4dl_tpu.parallel.sp_pipeline import (
            SPPipeline,
            init_sp_pipeline_state,
            make_sp_gems_train_step,
            make_sp_pipeline_train_step,
        )

        groups = (2 * cfg.times * cfg.parts) if family == "gems_sp" else cfg.parts
        assert cfg.batch_size % groups == 0, (cfg.batch_size, groups)
        micro = cfg.batch_size // groups
        spp = SPPipeline.build(
            model, params, max(cfg.split_size, 2), sp, microbatch=micro,
            junction=junction, balance=cfg.balance, compute_dtype=dtype,
            levels=levels, local_dp=local_dp, param_dtype=pdtype,
        )
        with rec.span("setup/make_step"):
            if family == "gems_sp":
                step = make_sp_gems_train_step(
                    spp, opt, mesh, cfg.parts, times=cfg.times,
                    compute_dtype=dtype, remat=cfg.remat, from_probs=from_probs,
                    with_data_axis=dp > 1, donate=True, schedule=cfg.schedule,
                    quant=quant,
                )
            else:
                step = make_sp_pipeline_train_step(
                    spp, opt, mesh, cfg.parts, compute_dtype=dtype,
                    remat=cfg.remat, from_probs=from_probs,
                    with_data_axis=dp > 1, donate=True, schedule=cfg.schedule,
                    quant=quant,
                )
        with rec.span("setup/place_state"):
            state = init_sp_pipeline_state(spp, params, opt, mesh)
        return (
            step, state,
            (lambda s: spp.unpack_all(
                jax.device_get(s.sp_buf), jax.device_get(s.tail_buf))),
            cfg.batch_size * dp,
        )


def _ensure_devices(need: int) -> None:
    """Provision ``need`` virtual CPU devices — only where ``JAX_PLATFORMS``
    names the CPU and no backend is initialized yet.  On any other platform
    nothing is provisioned: were the accelerator to fail to initialize, jax
    would fall back to the CPU and a provisioned run would train there and
    exit 0; a mesh larger than ``jax.devices()`` is instead the error
    ``build_mesh`` raises.

    A fleet leg is pinned to its slice: when the scheduler set
    ``MPI4DL_FLEET_SLICE_DEVICES`` the process provisions EXACTLY that many
    devices — the slice IS the job's world, and over-provisioning would let
    a 4-device tenant silently compile onto its neighbor's devices."""
    if "cpu" not in os.environ.get("JAX_PLATFORMS", "").lower().split(","):
        return
    cap = os.environ.get("MPI4DL_FLEET_SLICE_DEVICES", "")
    pinned = int(cap) if cap.isdigit() and int(cap) > 0 else None
    if pinned is None and need <= 1:
        return
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        return
    from mpi4dl_tpu.compat import ensure_host_device_count

    ensure_host_device_count(pinned if pinned is not None else max(need, 8))


def _open_telemetry(directory, family, cfg, spec, step, state, dataset,
                    global_batch, argv):
    """Open a RunLog and write the meta + compiled-step cost records.

    The cost record lowers and compiles the step once more through the AOT
    path (``step.lower(...).compile()``) to reach ``cost_analysis()`` and
    the collective-bearing HLO text — an extra compile the flag opts into
    (the persistent compilation cache absorbs it where enabled).  Failures
    degrade to a ``cost_error`` record: telemetry must never kill a run."""
    from mpi4dl_tpu.obs import RunLog

    runlog = RunLog.create(directory, prefix=f"{family}-{cfg.model}")
    runlog.write_meta(
        config=cfg, mesh_spec=spec, family=family,
        argv=list(argv) if argv is not None else sys.argv[1:],
    )
    try:
        import jax

        from mpi4dl_tpu.obs import (
            arithmetic_intensity, compiled_cost, hlo_collective_stats,
            peak_flops,
        )

        x, y = dataset.batch(0, global_batch)
        compiled = step.lower(state, x, y).compile()
        cost = compiled_cost(compiled)
        hlo_text = compiled.as_text()
        coll = hlo_collective_stats(hlo_text)
        # Schedule fingerprint: which per-tick scopes the compiled program
        # carries (obs/report.py renders them on the `pipeline:` line).
        tick_scopes = sorted(
            s for s in ("gpipe_scan", "pp_1f1b_scan", "gems_dual_scan",
                        "gems_1f1b_scan", "tail_scan", "fwd_tick", "bwd_tick")
            if s in hlo_text
        )
        # Cost-model flops are PER DEVICE (the one SPMD module every device
        # executes), so the report's MFU divides by one device's peak.
        peak, src = peak_flops(jax.devices()[0], allow_cpu_nominal=True)
        runlog.write(
            "cost",
            flops=cost["flops"],
            bytes_accessed=cost["bytes_accessed"],
            arithmetic_intensity=arithmetic_intensity(
                cost["flops"], cost["bytes_accessed"]
            ),
            collectives=coll,
            tick_scopes=tick_scopes,
            peak_flops=peak,
            peak_source=src,
            device_count=len(jax.devices()),
        )
    except Exception as e:  # noqa: BLE001 — telemetry must never kill a run
        runlog.write("cost_error", error=repr(e))
        print(f"note: telemetry cost analysis unavailable ({e})")
    return runlog


def run(family: str, model: str, argv=None) -> dict:
    """Parse flags and run the benchmark; returns the final summary dict."""
    import jax
    import numpy as np

    parser = get_parser()
    parser.set_defaults(model=model)
    parser.add_argument("--steps-per-epoch", type=int, default=10)
    parser.add_argument(
        "--profile-dir", default=None,
        help="write a jax.profiler trace of the epoch loop (TensorBoard/XProf"
             " format) — the TPU analog of the reference's CUDA-event phase "
             "timing (benchmark_resnet_gems_master_with_sp.py:417-440)",
    )
    parser.add_argument(
        "--telemetry-dir", default=None,
        help="write a RunLog JSONL (run metadata + per-step records + "
             "compiled-step cost/collective accounting) under this "
             "directory; render with `python -m mpi4dl_tpu.obs report` "
             "(docs/observability.md)",
    )
    parser.add_argument(
        "--watchdog-secs", type=float, default=None,
        help="step wall-clock budget: a step (batch fetch + device step) "
             "exceeding it dumps live Python stacks + the last RunLog "
             "record to stderr (default: MPI4DL_WATCHDOG_SECS, else off; "
             "docs/resilience.md)",
    )
    parser.add_argument(
        "--watchdog-compile-secs", type=float, default=None,
        help="watchdog budget for the FIRST step (the one that pays the "
             "XLA compile; default: MPI4DL_WATCHDOG_COMPILE_SECS, else 10x "
             "the step budget; docs/resilience.md)",
    )
    args = parser.parse_args(argv)
    cfg = config_from_args(args)
    if cfg.verbose:
        # Reference --verbose enables stdlib logging (benchmark scripts,
        # e.g. benchmark_amoebanet_sp.py:41-42); force=True because jax/absl
        # may already have attached root handlers.
        import logging

        logging.basicConfig(level=logging.DEBUG, force=True)
    if cfg.enable_master_comm_opt:
        print(
            "note: --enable-master-comm-opt is a no-op here — the one-weight-"
            "set GEMS redesign cannot diverge, so the reference's MASTER-OPT "
            "param/grad exchange (train_spatial_master.py:229-455) has "
            "nothing to synchronize."
        )

    from mpi4dl_tpu.compat import ensure_compilation_cache
    from mpi4dl_tpu.data import make_dataset
    from mpi4dl_tpu.mesh import MeshSpec, build_mesh

    spec = MeshSpec.from_config(cfg) if family != "lp" and family != "gems" else (
        MeshSpec(data=cfg.data_parallel, stage=max(cfg.split_size, 1))
    )
    _ensure_devices(spec.size)
    ensure_compilation_cache()
    devices = jax.devices()
    print(f"devices: {len(devices)} x {devices[0].platform}; mesh {spec}")
    mesh = build_mesh(spec, devices)

    step, state, eval_params_fn, global_batch = build_train(cfg, family, mesh)

    # Optional checkpoint resume (reference has no checkpointing; SURVEY §5
    # plans it as a new capability).  restore_latest returns the step id the
    # checkpoint was taken at, so a resumed run continues the global step
    # count and batch sequence instead of restarting at 0.
    ckpt_mgr = None
    start_step = 0
    if cfg.checkpoint_dir:
        from mpi4dl_tpu.checkpoint import (
            CheckpointManager, config_fingerprint, split_config_fingerprint,
        )
        from mpi4dl_tpu.quant import QuantPolicy

        # steps_per_epoch is fingerprinted as model IDENTITY: it defines the
        # global-step → batch-index mapping and the checkpoint cadence, so
        # resuming with a different value would replay different data while
        # claiming the bit-identical-resume contract.  The LAYOUT side
        # (mesh, parts, schedule, spatial placement, quant/stripe policy —
        # RESOLVED, so a hatch override is a recorded layout change, not
        # silent drift) may differ between save and restore: elastic restore
        # re-places every leaf under this run's mesh (docs/resilience.md).
        quant_resolved = QuantPolicy.resolve(cfg.quant_collectives)
        identity_fp, layout_fp, layout_desc = split_config_fingerprint(
            cfg, spec,
            extra_identity={"steps_per_epoch": args.steps_per_epoch},
            extra_layout={
                "quant_resolved": (
                    quant_resolved.spec() if quant_resolved else "off"
                ),
                "stripe_bwd_resolved": os.environ.get(
                    "MPI4DL_STRIPE_BWD", "0"
                ),
            },
        )
        ckpt_mgr = CheckpointManager(
            cfg.checkpoint_dir,
            fingerprint=config_fingerprint(
                cfg, spec, {"steps_per_epoch": args.steps_per_epoch}
            ),
            identity=identity_fp, layout=layout_fp, layout_desc=layout_desc,
        )
        state, start_step = ckpt_mgr.restore_latest(state)
        if start_step:
            print(f"resuming from checkpoint step {start_step}")
        if ckpt_mgr.last_restore is not None and ckpt_mgr.last_restore.elastic:
            print(
                "note: ELASTIC restore — checkpoint was saved under a "
                f"different layout ({ckpt_mgr.last_restore.saved_layout}); "
                "leaves re-placed under this run's mesh"
            )

    dataset = make_dataset(cfg)
    steps = args.steps_per_epoch
    # warmup_steps=1: the first step pays compilation; StepMeter drops it
    # explicitly (and reports the drop count) instead of the old implicit
    # `epoch > 0 or i > 0` skip.
    meter = StepMeter(global_batch, warmup_steps=1)

    runlog = None
    if args.telemetry_dir:
        runlog = _open_telemetry(
            args.telemetry_dir, family, cfg, spec, step, state, dataset,
            global_batch, argv,
        )
        if ckpt_mgr is not None and ckpt_mgr.last_restore is not None:
            runlog.write("restore", **ckpt_mgr.last_restore.record())

    # The supervised loop (mpi4dl_tpu/resilience/loop.py) owns the epoch
    # structure: anomaly guard + rollback, preemption-safe checkpointing
    # through the background writer, fault injection, step watchdog.
    from mpi4dl_tpu.resilience import AnomalyGuard, FaultInjector, run_supervised
    from mpi4dl_tpu.resilience.watchdog import watchdog_budget_from_env

    if start_step >= cfg.num_epochs * steps:
        print(
            f"note: checkpoint step {start_step} already covers "
            f"{cfg.num_epochs} epoch(s) x {steps} steps — nothing to run"
        )

    # try/finally: a crash mid-epoch must still flush the profiler trace
    # (start_trace only buffers; stop_trace writes the files — the crash you
    # wanted to profile would otherwise leave an empty trace dir) and close
    # the telemetry sink.
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    try:
        result = run_supervised(
            step, state, dataset,
            global_batch=global_batch,
            steps_per_epoch=steps,
            num_epochs=cfg.num_epochs,
            num_workers=cfg.num_workers,
            start_step=start_step,
            ckpt=ckpt_mgr,
            runlog=runlog,
            meter=meter,
            print_fn=print,
            profile=bool(args.profile_dir),
            guard=AnomalyGuard.from_env(),
            faults=FaultInjector.from_env(),
            watchdog_secs=watchdog_budget_from_env(args.watchdog_secs),
            watchdog_compile_secs=args.watchdog_compile_secs,
        )
    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()
            print(f"profile trace written to {args.profile_dir}")
        if runlog is not None:
            runlog.write("summary", **meter.stats())
            runlog.close()
            print(f"telemetry written to {runlog.path} "
                  f"(render: python -m mpi4dl_tpu.obs report {runlog.path})")
            try:
                from mpi4dl_tpu.obs.metrics import write_metrics_file
                from mpi4dl_tpu.obs.runlog import read_runlog

                prom = os.path.splitext(runlog.path)[0] + ".prom"
                write_metrics_file(read_runlog(runlog.path), prom)
                print(f"metrics snapshot written to {prom}")
            except Exception as e:  # noqa: BLE001  # analysis: ok(swallow-except)
                # deliberate: telemetry must never kill a run
                print(f"note: metrics snapshot unavailable ({e})")
    print(meter.summary())
    return {
        "images_per_sec": meter.images_per_sec(),
        "loss": result.metrics.get("loss", float("nan")),
        "steps": len(meter.times_ms),
        "final_step": result.final_step,
        "start_step": start_step,
        "preempted": result.preempted,
        "anomalies": result.anomalies,
        # How many devices hold the final state (a mesh that was asked for
        # and not used shows here; chip_smoke.py checks it).
        "state_devices": len({
            d for leaf in jax.tree.leaves(result.state) for d in leaf.devices()
        }),
        "elastic": bool(
            ckpt_mgr is not None and ckpt_mgr.last_restore is not None
            and ckpt_mgr.last_restore.elastic
        ),
        "telemetry_path": runlog.path if runlog is not None else None,
    }
