"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

One process, no child, no CPU path.  With no arguments it needs one TPU chip:

1. the trainer at the full width of AmoebaNet-D(18, 416) at 1024² bs 1 bf16,
   through the normal entry point ``benchmarks.common.run`` (1 compiling
   step + 3 more);
2. the Pallas attention kernel, compiled (never interpreted), at a real
   width, against its plain reference.

``--four-chips`` needs four chips and runs only what exists across chips,
each leg beside the one-device run it is compared with: leg A, the 2x2
spatial trainer; leg B, the 4-stage GPipe pipeline (depth cut to 12 cells,
one step — see PIPELINE_MODEL).

The last line of stdout is one JSON object, ``{"ok": true, "device":
{"platform": "tpu", "kind": ..., "count": ...}}``, printed only when every
phase passed; any failure exits non-zero without it.  Everything else worth
reading (compile seconds, step times, losses, per-device memory) is on
earlier lines — smoke observations, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import re
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)

# AmoebaNet-D(18, 416) at the reference's flagship 1024² — the widths of the
# model the paper charts; only the step count is cut.
FULL_MODEL = dict(num_layers=18, num_filters=416, image_size=1024,
                  num_classes=1000)

# Leg B's depth is cut from 18 cells to 12, widths as published: at 18 the
# chip's compiler refuses the 4-stage step ("Used 19.70G of 15.75G hbm": the
# temporaries of the flat stage buffer, whose rows are as long as the widest
# stage); at 12 it needs 8.27 GiB a device (compiles for a described v5e:2x2,
# PR 22).  One step only: every shard_map engine retraces on step 2, and a
# second five-minute compile on four chips buys no new check.
PIPELINE_MODEL = dict(FULL_MODEL, num_layers=12)
PIPELINE_STEPS = 1

# bf16 compute, fp32 loss: two layouts of the same step differ by summation
# order only.  Stated tolerances, relative to the reference loss.
LOSS_RTOL_FIRST_STEP = 2e-2   # before any update
LOSS_RTOL_LATER_STEPS = 5e-2  # after up to three updates
# Kernel vs plain reference at highest matmul precision, relative L2.
KERNEL_RTOL = 2e-2

# The kernel's shapes: a 4096-token, 128-wide head.  tests/test_tpu_compile.py
# compiles the same shapes for a described v5e.
KERNEL_SHAPES = dict(seq=4096, head_dim=128, heads=8)

_STEP_LINE = re.compile(
    r"^epoch \d+ step \d+ time_ms ([0-9.]+) images_per_sec \S+ loss (\S+) acc"
)


class SmokeFailure(Exception):
    """A phase ran and its result is wrong."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class _Tee(io.TextIOBase):
    def __init__(self, *sinks):
        self._sinks = sinks

    def write(self, s: str) -> int:
        for sink in self._sinks:
            sink.write(s)
        return len(s)

    def flush(self) -> None:
        for sink in self._sinks:
            sink.flush()


class _CompileWatch:
    """Counts the programs XLA's backend compiled while active, and their
    seconds (jax.monitoring's backend-compile duration event; a persistent-
    cache hit does not fire it)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.large = 0  # took a second or more: step programs, not helpers
        self.secs = 0.0

    def __call__(self, event: str, duration_secs: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.large += duration_secs >= 1.0
            self.secs += duration_secs

    def __enter__(self) -> "_CompileWatch":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self)


def _peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` per device; None where the backend keeps no
    memory statistics (the CPU)."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(None if not stats else int(stats["peak_bytes_in_use"]))
    return out


def train(family: str, *, steps: int, batch_size: int, extra=(),
          num_layers: int, num_filters: int, image_size: int,
          num_classes: int) -> dict:
    """One ``benchmarks.common.run`` of the AmoebaNet trainer: ``steps``
    steps (the first compiles) of synthetic data in bf16.  Returns run()'s
    summary plus the per-step times and losses read off its step lines and
    the backend-compile count; raises SmokeFailure unless the run is whole
    and finite."""
    from benchmarks.common import run

    argv = [
        "--num-layers", str(num_layers), "--num-filters", str(num_filters),
        "--image-size", str(image_size), "--num-classes", str(num_classes),
        "--batch-size", str(batch_size), "--precision", "bf_16",
        "--steps-per-epoch", str(steps), *extra,
    ]
    print(f"[smoke] run({family!r}, 'amoebanet', {' '.join(argv)})")
    buf = io.StringIO()
    t0 = time.perf_counter()
    with _CompileWatch() as compiles, \
            contextlib.redirect_stdout(_Tee(sys.stdout, buf)):
        out = run(family, "amoebanet", argv)
    wall = time.perf_counter() - t0
    lines = [m for m in map(_STEP_LINE.match, buf.getvalue().splitlines()) if m]
    times_ms = [float(m.group(1)) for m in lines]
    losses = [float(m.group(2)) for m in lines]
    print(f"[smoke] {family}: wall {wall:.1f}s; backend compiles "
          f"{compiles.count}, {compiles.large} of them >= 1 s "
          f"({compiles.secs:.1f}s in all); compile+first step "
          f"{times_ms[0] / 1e3 if times_ms else float('nan'):.1f}s; later "
          f"steps ms {[round(t, 1) for t in times_ms[1:]]}; losses {losses}")
    _check(len(losses) == steps, f"{family}: {len(losses)} step lines, "
                                 f"asked for {steps}")
    _check(all(math.isfinite(v) for v in losses) and math.isfinite(out["loss"]),
           f"{family}: non-finite loss {losses} / {out['loss']}")
    _check(out["final_step"] == steps,
           f"{family}: final_step {out['final_step']} != {steps}")
    _check(out["anomalies"] == 0, f"{family}: {out['anomalies']} anomalies")
    _check(not out["preempted"], f"{family}: preempted")
    _check(steps == 1 or len(set(losses)) > 1,
           f"{family}: loss did not change over {steps} steps: {losses}")
    return dict(out, times_ms=times_ms, losses=losses,
                compiles=compiles.count, large_compiles=compiles.large,
                compile_secs=compiles.secs)


def compare_losses(name: str, got: dict, ref: dict) -> None:
    """Per-step losses of two layouts of the same training run.  Step 0 is
    the loss before any update — the tightest form — and is checked first."""
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"])):
        rtol = LOSS_RTOL_FIRST_STEP if i == 0 else LOSS_RTOL_LATER_STEPS
        rel = abs(a - b) / max(abs(b), 1e-6)
        print(f"[smoke] {name}: step {i} loss {a:.4f} vs one-device {b:.4f} "
              f"(rel diff {rel:.2e}, tolerance {rtol:.0e})")
        _check(rel <= rtol, f"{name}: step {i} loss {a} vs {b}: rel diff "
                            f"{rel:.3e} > {rtol}")


def one_chip_trainer(*, steps: int = 4, batch_size: int = 1, **model) -> dict:
    """The default phase: the one-device trainer (``make_train_step``, no
    shard_map) through the runner."""
    import jax

    result = train("lp", steps=steps, batch_size=batch_size, **model)
    peak = _peak_bytes(jax.devices()[:1])[0]
    print("[smoke] one-chip trainer: peak_bytes_in_use "
          + ("not reported by this backend" if peak is None
             else f"{peak} ({peak / 2**30:.2f} GiB)"))
    return result


def four_chip_spatial(*, steps: int = 4, batch_size: int = 1, **model) -> None:
    """Leg A: spatial parallelism on the 2x2 tile mesh ``MeshSpec.from_config``
    derives, against the one-device trainer in the same process (same seed,
    batch and steps).  The spatial run goes first so the per-device peaks
    read after it are its own."""
    import jax

    devices = jax.devices()[:4]
    sp = train("sp", steps=steps, batch_size=batch_size, **model,
               extra=("--num-spatial-parts", "4", "--slice-method", "square"))
    sp_peaks = _peak_bytes(devices)
    ref = train("lp", steps=steps, batch_size=batch_size, **model)
    compare_losses("leg A (SP 2x2)", sp, ref)
    _check(sp["state_devices"] == 4,
           f"leg A: state lives on {sp['state_devices']} devices, not 4")
    print(f"[smoke] leg A: per-device peak bytes after the SP run {sp_peaks}")
    if any(p is None for p in sp_peaks):
        # CPU mesh (tests): no memory statistics.  On a TPU main() refuses
        # this before any phase runs.
        print("[smoke] leg A: memory not reported by this backend")
        return
    _check(all(p > 0 for p in sp_peaks),
           f"leg A: a device reports no memory in use: {sp_peaks}")
    # The four tiles are the same program on equal shares of the image: a
    # device that held much less than another did not get its share.  (No
    # comparison with the one-device run: it remats per cell and the spatial
    # step does not, so a tile may well peak above it.)
    _check(min(sp_peaks) >= 0.5 * max(sp_peaks),
           f"leg A: per-device peaks are uneven, the work is not spread "
           f"over the four tiles: {sp_peaks}")


def four_chip_pipeline(*, steps: int = 4, batch_size: int = 4, **model) -> None:
    """Leg B: the 4-stage GPipe engine with 4 micro-batches, against the
    one-device trainer accumulating the same 4 micro-batches."""
    import jax

    pp = train("lp", steps=steps, batch_size=batch_size, **model,
               extra=("--split-size", "4", "--parts", "4"))
    ref = train("lp", steps=steps, batch_size=batch_size, **model,
                extra=("--parts", "4"))
    compare_losses("leg B (GPipe 4 stages)", pp, ref)
    _check(pp["state_devices"] == 4,
           f"leg B: state lives on {pp['state_devices']} devices, not 4")
    # Peaks are the process's high-water marks (leg A ran first): printed,
    # not checked.
    print(f"[smoke] leg B: per-device peak bytes so far "
          f"{_peak_bytes(jax.devices()[:4])}")


def _rel_l2(got, ref) -> float:
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))


def _kernel_check(name: str, got, ref) -> None:
    import jax
    import jax.numpy as jnp

    for i, (g, r) in enumerate(zip(jax.tree.leaves(got), jax.tree.leaves(ref))):
        _check(g.shape == r.shape, f"{name}[{i}]: shape {g.shape} vs {r.shape}")
        _check(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))),
               f"{name}[{i}]: non-finite values")
        err = _rel_l2(g, r)
        print(f"[smoke] kernel {name}[{i}] {tuple(g.shape)}: rel L2 error "
              f"{err:.2e} (tolerance {KERNEL_RTOL:.0e})")
        _check(err <= KERNEL_RTOL, f"{name}[{i}]: rel L2 error {err:.3e}")


def flash_attention(q, k, v):
    """Causal attention of [BH, T, D] through the compiled ``block_flash``
    kernel (default tiles), normalized — what both the chip phase and the
    compile-for-v5e test differentiate."""
    from mpi4dl_tpu.ops.pallas_attention import block_flash

    o, _, l = block_flash(q, k, v, 0, 0, True, 1.0 / math.sqrt(q.shape[-1]),
                          256, 512, False)
    return o / l[..., None]


def kernels(*, seq: int, head_dim: int, heads: int) -> None:
    """The Pallas attention kernel through Mosaic (``interpret=False`` — this
    phase has no CPU form), forward and backward, against its plain XLA
    reference at highest matmul precision."""
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.ops.pallas_attention import _reference_mlo

    kq, kk, kv, kc = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(kq, (heads, seq, head_dim), jnp.bfloat16)
    k = jax.random.normal(kk, (heads, seq, head_dim), jnp.bfloat16)
    v = jax.random.normal(kv, (heads, seq, head_dim), jnp.bfloat16)

    def ref(q, k, v):
        with jax.default_matmul_precision("highest"):
            o, _, l = _reference_mlo(q, k, v, 0, 0, True,
                                     1.0 / math.sqrt(head_dim))
        return o / l[..., None]

    ct = jax.random.normal(kc, (heads, seq, head_dim), jnp.float32)

    def fwd_bwd(f):
        @jax.jit
        def g(q, k, v):
            out, vjp = jax.vjp(f, q, k, v)
            return out, vjp(ct)
        return g

    t0 = time.perf_counter()
    out, grads = jax.block_until_ready(fwd_bwd(flash_attention)(q, k, v))
    print(f"[smoke] block_flash fwd+bwd compiled+ran in "
          f"{time.perf_counter() - t0:.1f}s")
    out_ref, grads_ref = fwd_bwd(ref)(q, k, v)
    _kernel_check("block_flash fwd", out, out_ref)
    _kernel_check("block_flash bwd (dq, dk, dv)", grads, grads_ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip legs (2x2 spatial, 4-stage "
                         "pipeline) and their one-device references only")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    need = 4 if args.four_chips else 1
    if dev.platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); jax found "
              f"{len(devices)} x {dev.platform}. There is no CPU path.",
              file=sys.stderr)
        return 2
    from mpi4dl_tpu.compat import ensure_compilation_cache

    cache = ensure_compilation_cache()
    print(f"[smoke] devices: {len(devices)} x {dev.platform} ({dev.device_kind})"
          f"; jax {jax.__version__}; compile cache "
          f"{cache or os.environ['JAX_COMPILATION_CACHE_DIR']}")
    if any(p is None for p in _peak_bytes(devices[:need])):
        print("chip_smoke: this TPU reports no memory statistics",
              file=sys.stderr)
        return 2
    phases = (
        [("leg A: spatial 2x2", lambda: four_chip_spatial(**FULL_MODEL)),
         ("leg B: GPipe 4 stages", lambda: four_chip_pipeline(
             steps=PIPELINE_STEPS, **PIPELINE_MODEL))]
        if args.four_chips else
        [("one-chip trainer", lambda: one_chip_trainer(**FULL_MODEL)),
         ("pallas kernel", lambda: kernels(**KERNEL_SHAPES))]
    )
    failed = []
    for name, phase in phases:
        print(f"[smoke] === {name} ===")
        t0 = time.perf_counter()
        try:
            phase()
            print(f"[smoke] {name}: PASSED in {time.perf_counter() - t0:.1f}s")
        except Exception as e:  # a failed phase must not hide the next one's
            import traceback

            traceback.print_exc()
            print(f"[smoke] {name}: FAILED ({type(e).__name__}: {e})")
            failed.append(name)
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
