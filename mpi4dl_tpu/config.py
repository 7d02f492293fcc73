"""Configuration / flags.

Mirrors the reference's single shared argparse parser
(``src/torchgems/parser.py:21-143``) so users of the reference find the same
vocabulary, plus TPU-specific knobs (mesh shape, dtype, D2 fusion, BN scope).
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Dict, List, Optional, Tuple


# ---------------------------------------------------------------------------
# Environment-hatch registry
#
# Every ``MPI4DL_*`` environment escape hatch the package (or its benches /
# tests) reads must be declared here.  The static analyzer
# (mpi4dl_tpu/analysis, rule ``env-hatch``) enforces both directions: an
# ``os.environ`` read of an undeclared ``MPI4DL_*`` name is a violation, and a
# declared hatch that is never read anywhere is a dead flag.  The README's
# "Environment hatches" section is generated from this table
# (:func:`hatches_markdown`).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Hatch:
    """One declared environment escape hatch."""

    name: str
    default: str  # the effective default when the variable is unset
    doc: str
    internal: bool = False  # process-internal plumbing, not a user knob


HATCHES: Dict[str, Hatch] = {
    h.name: h
    for h in (
        Hatch("MPI4DL_SQRT_GROUPS", "0",
              "Remat cell-group count for remat='sqrt'; 0 = auto (~sqrt(n); "
              "bench.py pins 16 for ResNet)."),
        Hatch("MPI4DL_REMAT_OPS", "0",
              "1 = per-op checkpoints inside composite cells under ANY outer "
              "remat level (the ResNet-2048 memory frontier)."),
        Hatch("MPI4DL_1F1B_CELL_REMAT", "auto",
              "Per-cell checkpoints inside the 1F1B backward branches: "
              "1 = force on, 0 = force off, auto = on only for short stages "
              "(<= 3 cells — measured crossover, docs/pipeline.md; deep "
              "stages schedule the per-cell recomputes concurrently and "
              "regress peak HBM several-fold)."),
        Hatch("MPI4DL_NO_PHASE_DX", "0",
              "1 = strided convs keep XLA's lhs-dilation backward instead of "
              "the phase-decomposed dx path."),
        Hatch("MPI4DL_NO_HSTRIPE", "0",
              "1 = tiny-channel huge-spatial convs keep the plain XLA conv "
              "instead of the W-fold or H-striped patching, and no run of "
              "layers between them is carried folded."),
        Hatch("MPI4DL_HSTRIPE_RUN", "auto",
              "Block-level H-striping control: 0 = off, 1 = on (silences the "
              "train-mode BN stats warning), auto = on with warning."),
        Hatch("MPI4DL_HSTRIPE_EXACT", "0",
              "1 = striped train-mode BN uses GLOBAL batch statistics "
              "(exactness at ~1 extra prefix forward per BN; applies to "
              "both the single-device striped run and the stripe-wise "
              "backward)."),
        Hatch("MPI4DL_STRIPE_BWD", "0",
              "Stripe-wise forward+backward through eligible stride-1 "
              "blocks (ops/stripe_bwd.py): 1 = spatially sharded blocks "
              "only (the SP region — tail cells excluded: striped scans "
              "inside the 1F1B branch conditionals regress peak HBM "
              "several-fold), all = every eligible block (exactness "
              "testing).  The accumulated halo is realized once, then a "
              "jax.checkpoint'd scan over H stripes bounds the BACKWARD "
              "working set to one stripe — the SP-region O(parts) buy-back "
              "at the 8K flagship (docs/pipeline.md)."),
        Hatch("MPI4DL_STRIPE_BUDGET", str(64 * 1024 * 1024),
              "Per-stripe working-set budget in bytes for the stripe-wise "
              "backward (widest intermediate per stripe, whole chunk); "
              "the stripe count is derived from it."),
        Hatch("MPI4DL_NO_PACK", "0",
              "1 = disable boundary packing of D2 fused-run margins "
              "(A/B hatch; measured a no-op on v5e — PERF_NOTES r5)."),
        Hatch("MPI4DL_LANE_PAD", "0",
              "1 = pad AmoebaNet bottleneck mid-channels to 128 lanes "
              "(vector-lane utilization A/B)."),
        Hatch("MPI4DL_NO_SCOPES", "0",
              "1 = disable obs trace scopes (jax.named_scope semantic names "
              "in traces/HLO), host step annotations and the span recorder "
              "(obs/spans.py; the crash marker's phase words stay) — "
              "pristine A/B compiles."),
        Hatch("MPI4DL_QUANT_COLLECTIVES", "<unset>",
              "Quantized-collective policy override (wins over --quant when "
              "set): `off`, one mode for every class (`int8`|`fp8`|`int4`), "
              "or per-class `junction=int4,respatial=int8,grad=int8,"
              "handoff=int8[,block=N]` — per-block-scaled payloads on the "
              "junction/respatial/grad/handoff wire classes "
              "(docs/quantization.md)."),
        Hatch("MPI4DL_NO_RESPATIAL_FAST", "0",
              "1 = disable the gather-free respatial fast paths (refine = "
              "local slice, coarsen = intra-group ring) and keep the legacy "
              "full-gather + slice reshard for A/B comparison."),
        Hatch("MPI4DL_FAULT", "<unset>",
              "Deterministic fault injection: `<kind>@<step>[:arg]` with "
              "kind in nan_loss|nan_batch|raise|sigterm|corrupt_ckpt|"
              "lost_shard_files|reshape|stall_data|oom_compile|oom_step|"
              "mesh_shrunk|slow_step|io_error — drives "
              "tests/test_resilience.py and the CI kill-and-resume + "
              "resilience-drill + supervisor-drill jobs "
              "(docs/resilience.md)."),
        Hatch("MPI4DL_CKPT_HOST_BYTES", str(1 << 30),
              "Byte budget for gathered-but-unwritten checkpoint shards in "
              "the async writer (sharded format): the training thread "
              "blocks instead of materializing more than this on the host, "
              "so peak save RSS is O(budget + largest shard), not O(full "
              "state) (docs/resilience.md)."),
        Hatch("MPI4DL_WATCHDOG_SECS", "0",
              "Step watchdog wall-clock budget in seconds (0 = off): a step "
              "(batch fetch + device step) exceeding it dumps live Python "
              "stacks + the last RunLog record to stderr "
              "(`--watchdog-secs` overrides)."),
        Hatch("MPI4DL_WATCHDOG_COMPILE_SECS", "10x step budget",
              "Watchdog budget for the FIRST step of a process (the one "
              "that pays the multi-minute XLA compile) — disarms after the "
              "first completed step, so realistic step budgets no longer "
              "false-trigger stall dumps during compile "
              "(`--watchdog-compile-secs` overrides; docs/resilience.md)."),
        Hatch("MPI4DL_WATCHDOG_ESCALATE", "0",
              "Watchdog escalation count (0 = dump forever): once one armed "
              "step has produced this many stall dumps, the watchdog writes "
              "a typed `hang` crash marker and exits the leg (status 82) so "
              "the supervisor can classify and relaunch instead of hanging "
              "until the scheduler kills it (docs/resilience.md)."),
        Hatch("MPI4DL_SUPERVISE_MAX_ATTEMPTS", "6",
              "Elastic supervisor: total training-leg launches before "
              "giving up (per-failure-class bounds apply on top — "
              "docs/resilience.md, policy matrix)."),
        Hatch("MPI4DL_SUPERVISE_BACKOFF", "1.0",
              "Elastic supervisor: base seconds of the exponential "
              "retry backoff (doubles per same-class recurrence, "
              "jittered +-25%)."),
        Hatch("MPI4DL_SUPERVISE_BACKOFF_CAP", "30",
              "Elastic supervisor: backoff ceiling in seconds (the "
              "exponential curve clamps here before jitter)."),
        Hatch("MPI4DL_QUARANTINE_STEPS", "<unset>",
              "Comma-list of global steps the supervised loop SKIPS "
              "outright (fetch nothing, train nothing, `quarantine` RunLog "
              "record) — the supervisor's poison-batch exclusion after a "
              "nan_cluster leg (docs/resilience.md)."),
        Hatch("MPI4DL_CRASH_MARKER", "<unset>",
              "Internal: where a supervised leg writes its structured "
              "crash marker (phase, step, error) on the way down — the "
              "supervisor points it at a per-attempt file.", internal=True),
        Hatch("MPI4DL_FLEET_DEVICES", "8",
              "Fleet scheduler: size of the shared device pool the "
              "bin-packer carves into per-job slices "
              "(docs/resilience.md, fleet scheduler)."),
        Hatch("MPI4DL_FLEET_POISON_ATTEMPTS", "2",
              "Fleet scheduler: failed supervisor RUNS (not leg attempts) "
              "before a job is quarantined as poison instead of requeued — "
              "the containment that keeps a doomed job from starving the "
              "queue."),
        Hatch("MPI4DL_FLEET_JOB", "<unset>",
              "Internal: the owning fleet job id, stamped into every leg "
              "subprocess so its result summary (and evidence artifacts) "
              "are attributable — the cross-contamination check verifies "
              "evidence stayed in its lane.", internal=True),
        Hatch("MPI4DL_FLEET_SLICE_DEVICES", "<unset>",
              "Internal: slice size the fleet scheduler pins a leg to; the "
              "leg self-provisions EXACTLY this many virtual-mesh devices "
              "instead of the 8-device default.", internal=True),
        Hatch("MPI4DL_NO_GUARD", "0",
              "1 = disable the anomaly guard (per-step finite-loss check "
              "with rollback to the last good checkpoint and poison-batch "
              "skip)."),
        Hatch("MPI4DL_GUARD_GRAD_NORM", "0",
              "Grad-norm guard limit (float; 0 = off): a step reporting "
              "metrics['grad_norm'] above it triggers the same rollback as "
              "a non-finite loss."),
        Hatch("MPI4DL_FLIGHT_STEPS", "64",
              "Flight-recorder ring capacity: the last N step records "
              "(per-device memory watermarks, jit-cache probe) plus "
              "checkpoint/anomaly/quarantine/preempt events kept in memory "
              "and dumped as `flight.json` on anomaly, watchdog "
              "escalation, preemption, and crash-marker writes "
              "(docs/observability.md)."),
        Hatch("MPI4DL_NO_FLIGHT", "0",
              "1 = disable the flight recorder (no in-memory ring, no "
              "`flight.json` dumps; the supervisor loses its fourth "
              "evidence source)."),
        Hatch("MPI4DL_METRICS_PORT", "<unset>",
              "Default port for `python -m mpi4dl_tpu.obs metrics --serve` "
              "(stdlib HTTP endpoint exposing the OpenMetrics text on "
              "/metrics); unset = file-sink only."),
        Hatch("MPI4DL_TPU_NATIVE_DIR", "<alongside data_native.py>",
              "Directory holding the prebuilt native data-loader artifacts."),
    )
}


def hatches_markdown(include_internal: bool = False) -> str:
    """Render the registry as the README's "Environment hatches" table."""
    lines = [
        "| Hatch | Default | Effect |",
        "| --- | --- | --- |",
    ]
    for h in HATCHES.values():
        if h.internal and not include_internal:
            continue
        lines.append(f"| `{h.name}` | `{h.default}` | {h.doc} |")
    return "\n".join(lines)


@dataclasses.dataclass
class ParallelConfig:
    # --- model / problem (reference parser.py) ---
    # resnet | amoebanet | lfm2_moe | deepseek_v3 | granitemoehybrid | keye_vl2
    # | ouro
    model: str = "resnet"
    batch_size: int = 32
    parts: int = 1  # micro-batches per step (GPipe "parts")
    split_size: int = 1  # number of pipeline stages (LP splits)
    # Pipeline schedule: 'gpipe' (all-forward-then-all-backward, the
    # exactness oracle) or '1f1b' (one-forward-one-backward with a manual
    # schedule-level backward — O(stages) live activations instead of
    # O(parts); docs/pipeline.md).  Ignored by non-pipeline families.
    schedule: str = "gpipe"
    num_spatial_parts: Tuple[int, ...] = (4,)  # comma-list in the reference
    spatial_size: int = 1  # how many leading splits are spatial
    times: int = 1  # GEMS replication factor ("--times")
    image_size: int = 32
    num_epochs: int = 1
    num_layers: int = 18  # amoebanet cell count knob
    num_filters: int = 416
    num_classes: int = 10
    # --- token models (lfm2_moe, deepseek_v3, granitemoehybrid, keye_vl2,
    # ouro): the cut and the job; the published sizes are the model file's
    # own (models/lfm2.py, models/deepseek_v3.py, models/granitemoehybrid.py,
    # models/keye_vl2.py, models/ouro.py).  A sample is a sequence of seq_len
    # ids below vocab_size; of a routed model's published experts this
    # process holds experts_held, from expert_first (one chip's share under
    # expert parallelism; granitemoehybrid and ouro have no routed experts
    # and are not handed them).  The defaults are within every model's
    # published counts but ouro's vocabulary (49,152), the uncut model of
    # none but lfm2_moe's.
    seq_len: int = 128
    vocab_size: int = 65536
    experts_held: int = 64
    expert_first: int = 0
    balance: Optional[Tuple[int, ...]] = None  # per-stage cell counts
    halo_d2: bool = False  # fused-halo "design 2"
    # Margin-consuming layers per fused halo block in D2 (reference
    # --fused-layers); 0 = fuse maximal runs (best: fewest exchanges).
    fused_layers: int = 0
    local_dp_lp: int = 1  # LOCAL_DP_LP: DP degree inside LP stages
    slice_method: str = "square"  # square | vertical | horizontal
    app: int = 3  # 1=image folder, 2=cifar-like, 3=synthetic (reference APP)
    datapath: str = "./train"
    enable_master_comm_opt: bool = False  # GEMS MASTER-OPT analog
    num_workers: int = 0
    precision: str = "fp_32"  # fp_32 | bf_16 | bf_16_all (reference vocabulary)

    # --- TPU-native knobs (new) ---
    data_parallel: int = 1  # outer DP degree
    bn_cross_tile: bool = True  # BN stats across spatial tiles (fix) or per-tile (parity)
    softmax_in_model: bool = False  # reproduce reference double-softmax quirk
    enable_gems: bool = False
    lr: float = 0.001  # reference benchmarks use SGD(lr=0.001)
    momentum: float = 0.0
    optimizer: str = "sgd"
    remat: bool = True  # jax.checkpoint each stage application
    # Quantized-collective policy spec ("off" | "int8" | "fp8" | "int4" |
    # per-class "junction=int4,grad=int8[,block=N]"); resolved by
    # mpi4dl_tpu.quant.QuantPolicy.resolve (the MPI4DL_QUANT_COLLECTIVES
    # hatch overrides).  Off is bit-identical to the unquantized engines.
    quant_collectives: str = "off"
    # Stripe-wise backward through eligible stride-1 blocks (sets the
    # MPI4DL_STRIPE_BWD hatch for this process at build time): the SP-region
    # O(parts) buy-back — docs/pipeline.md, ops/stripe_bwd.py.
    stripe_bwd: bool = False
    # SP→LP junction placement: None = derive from the pipeline splits (the
    # historical behaviour), an int = explicit junction cell, "auto" =
    # resolve from the analytical placement frontier
    # (parallel/spatial.choose_spatial_until — the mem_probe
    # --sweep-junction frontier promoted to the default config chooser).
    spatial_until: Optional[object] = None
    verbose: bool = False  # debug logging (reference parser.py --verbose)
    checkpoint_dir: Optional[str] = None
    seed: int = 0

    @property
    def spatial_part_size(self) -> int:
        return self.num_spatial_parts[0]

    @property
    def is_token_model(self) -> bool:
        """Whether a sample is a sequence of ids: the model's own entry in
        ``models.MODELS`` says what it takes in."""
        from mpi4dl_tpu.models import input_kind

        return input_kind(self.model) == "tokens"

    @property
    def sample_shape(self) -> Tuple[int, ...]:
        """One sample of the input, without the batch axis."""
        if self.is_token_model:
            return (self.seq_len,)
        return (self.image_size, self.image_size, 3)

    @property
    def compute_dtype(self):
        import jax.numpy as jnp

        return jnp.bfloat16 if self.precision in ("bf_16", "bf_16_all") else jnp.float32

    @property
    def param_dtype(self):
        import jax.numpy as jnp

        return jnp.bfloat16 if self.precision == "bf_16_all" else jnp.float32

    def validate(self) -> None:
        from mpi4dl_tpu.utils import is_power_two

        # Reference verify_spatial_config (train_spatial.py:33-58): power-of-2
        # image size and per-tile sizes, legal slice method.
        assert self.slice_method in ("square", "vertical", "horizontal")
        if self.spatial_size > 0 and self.spatial_part_size > 1:
            assert is_power_two(self.image_size), "image_size must be a power of two"
            assert self.image_size % self.spatial_part_size == 0
            # Multi-level SP (reference num_spatial_parts="4,2"): later levels
            # must not grow and must embed in the level-0 grid (checked by
            # spatial_levels_for); LOCAL_DP_LP shards over the tile devices.
            for p in self.num_spatial_parts[1:]:
                assert p <= self.spatial_part_size, (
                    f"spatial levels must not grow: {self.num_spatial_parts}"
                )
                assert self.spatial_part_size % p == 0, (
                    f"level tile count {p} must divide {self.spatial_part_size}"
                )
            if self.local_dp_lp > 1:
                assert self.spatial_part_size % self.local_dp_lp == 0, (
                    f"--local-DP {self.local_dp_lp} must divide the "
                    f"{self.spatial_part_size} spatial-tile devices"
                )
        assert self.batch_size % self.parts == 0, "batch must divide into parts"
        if self.balance is not None:
            assert len(self.balance) == self.split_size
        if self.spatial_until is not None:
            assert self.spatial_until == "auto" or (
                isinstance(self.spatial_until, int) and self.spatial_until >= 1
            ), f"--spatial-until must be 'auto' or an int >= 1, got {self.spatial_until!r}"
        # Fail fast on a malformed quant spec (raises ValueError with the
        # offending token; the hatch override is resolved at build time).
        from mpi4dl_tpu.quant.policy import QuantPolicy

        QuantPolicy.parse(self.quant_collectives)


def is_tpu_backend() -> bool:
    """True on the TPU backend — the auto-enable predicate for the Pallas
    (Mosaic) kernel of ring attention's flash path (ops/ring.py)."""
    import jax

    return jax.default_backend() == "tpu"


def get_parser() -> argparse.ArgumentParser:
    """Argparse mirroring reference parser.py flag names."""
    p = argparse.ArgumentParser(description="mpi4dl_tpu benchmarks")
    p.add_argument("--model", type=str, default="resnet",
                   help="resnet | amoebanet (images) | lfm2_moe | deepseek_v3 "
                        "| granitemoehybrid | keye_vl2 | ouro (token models)")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--parts", type=int, default=1)
    p.add_argument("--split-size", type=int, default=1)
    p.add_argument("--schedule", choices=["gpipe", "1f1b"], default="gpipe",
                   help="pipeline schedule: gpipe (default) or 1f1b "
                        "(O(stages) live activations; docs/pipeline.md)")
    p.add_argument("--num-spatial-parts", type=str, default="4")
    p.add_argument("--spatial-size", type=int, default=1)
    p.add_argument("--times", type=int, default=1)
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--num-epochs", type=int, default=1)
    p.add_argument("--num-layers", type=int, default=18)
    p.add_argument("--num-filters", type=int, default=416)
    p.add_argument("--num-classes", type=int, default=10)
    p.add_argument("--seq-len", type=int, default=128,
                   help="token models: ids a sequence")
    p.add_argument("--vocab-size", type=int, default=65536,
                   help="token models: rows of the vocabulary held here, at "
                        "most the model's own (lfm2_moe 65536, deepseek_v3 "
                        "128256, granitemoehybrid 100352, keye_vl2 151936, "
                        "ouro 49152)")
    p.add_argument("--experts-held", type=int, default=64,
                   help="token models with routed experts: how many this "
                        "process holds (all of the model's is the uncut "
                        "layer: lfm2_moe 64, deepseek_v3 and keye_vl2 128); "
                        "ignored by granitemoehybrid and ouro, which have none")
    p.add_argument("--expert-first", type=int, default=0,
                   help="token models with routed experts: the first expert "
                        "held; ignored by granitemoehybrid and ouro")
    p.add_argument("--balance", type=str, default=None)
    # the reference spells it --halo-D2 (parser.py); accept both
    p.add_argument("--halo-d2", "--halo-D2", dest="halo_d2", action="store_true")
    p.add_argument("--verbose", action="store_true",
                   help="enable debug logging (reference parser.py --verbose)")
    p.add_argument("--fused-layers", type=int, default=0,
                   help="padded layers per fused D2 exchange; 0 = maximal")
    p.add_argument("--local-DP", dest="local_dp_lp", type=int, default=1)
    p.add_argument(
        "--slice-method",
        type=str,
        default="square",
        help="square | vertical | horizontal",
    )
    p.add_argument("--app", type=int, default=3)
    p.add_argument("--datapath", type=str, default="./train")
    p.add_argument("--enable-master-comm-opt", action="store_true")
    p.add_argument("--num-workers", type=int, default=0)
    p.add_argument("--precision", type=str, default="fp_32")
    # TPU-native additions
    p.add_argument("--data-parallel", type=int, default=1)
    p.add_argument("--per-tile-bn", action="store_true", help="reference-parity per-tile BN stats")
    p.add_argument("--softmax-in-model", action="store_true")
    p.add_argument("--enable-gems", action="store_true")
    p.add_argument("--lr", type=float, default=0.001)
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--quant", dest="quant_collectives", type=str,
                   default="off", metavar="SPEC",
                   help="quantized-collective policy: off (default, "
                        "bit-identical), int8|fp8|int4 for every hot class, "
                        "or per-class junction=...,respatial=...,grad=...,"
                        "handoff=...[,block=N] (docs/quantization.md)")
    p.add_argument("--stripe-bwd", action="store_true",
                   help="stripe-wise forward+backward through eligible "
                        "stride-1 blocks (sets MPI4DL_STRIPE_BWD=1): bounds "
                        "the SP-region backward working set to one H-stripe "
                        "— the O(parts) buy-back (docs/pipeline.md)")
    p.add_argument("--spatial-until", default=None, metavar="N|auto",
                   type=_spatial_until_arg,
                   help="SP->LP junction placement: an explicit cell index, "
                        "or 'auto' to resolve it from the analytical "
                        "placement frontier (the mem_probe --sweep-junction "
                        "chooser); default: derive from the pipeline splits")
    p.add_argument("--checkpoint-dir", type=str, default=None)
    p.add_argument("--seed", type=int, default=0)
    return p


def _int_tuple(s: Optional[str]) -> Optional[Tuple[int, ...]]:
    if s is None or s == "":
        return None
    return tuple(int(x) for x in s.split(","))


def _spatial_until_arg(s):
    """Parse --spatial-until: None, 'auto', or an int."""
    if s is None or s == "":
        return None
    if s == "auto":
        return "auto"
    return int(s)


def config_from_args(args: argparse.Namespace) -> ParallelConfig:
    cfg = ParallelConfig(
        model=args.model,
        batch_size=args.batch_size,
        parts=args.parts,
        split_size=args.split_size,
        schedule=args.schedule,
        num_spatial_parts=_int_tuple(args.num_spatial_parts) or (4,),
        spatial_size=args.spatial_size,
        times=args.times,
        image_size=args.image_size,
        num_epochs=args.num_epochs,
        num_layers=args.num_layers,
        num_filters=args.num_filters,
        num_classes=args.num_classes,
        seq_len=args.seq_len,
        vocab_size=args.vocab_size,
        experts_held=args.experts_held,
        expert_first=args.expert_first,
        balance=_int_tuple(args.balance),
        halo_d2=args.halo_d2,
        fused_layers=args.fused_layers,
        local_dp_lp=args.local_dp_lp,
        slice_method=args.slice_method,
        app=args.app,
        datapath=args.datapath,
        enable_master_comm_opt=args.enable_master_comm_opt,
        num_workers=args.num_workers,
        precision=args.precision,
        data_parallel=args.data_parallel,
        bn_cross_tile=not args.per_tile_bn,
        softmax_in_model=args.softmax_in_model,
        enable_gems=args.enable_gems,
        lr=args.lr,
        remat=not args.no_remat,
        quant_collectives=getattr(args, "quant_collectives", "off"),
        stripe_bwd=getattr(args, "stripe_bwd", False),
        spatial_until=_spatial_until_arg(getattr(args, "spatial_until", None)),
        verbose=getattr(args, "verbose", False),
        checkpoint_dir=args.checkpoint_dir,
        seed=args.seed,
    )
    cfg.validate()
    return cfg
