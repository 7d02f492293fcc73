"""A kernel's share of its roofline, in %: the least time the chip could take
for the work the kernel executed in a step (the larger of operations over the
chip's bf16 peak and bytes over its memory bandwidth, ``peaks.json``), over
the device time its instructions took (``trace_ops_ms``'s sum for ``scope``
or ``pattern``).  ``work`` names one of the functions below, which give the
operations and bytes a step executes; each states its factor for the forward
pass that per-cell remat runs twice, and takes the number of its ``layers``
from the metric's file (the configuration's: the process holds the sites of
two models, the step's and the reference check's, so the recorder's count of
sites is twice the step's).  None without a trace, a peak, the instructions,
or what the work is computed from.
"""

import os
import statistics

from perfbench.catalog import _load_module

_ops = _load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "trace_ops_ms.py"),
    "perfbench_reader_trace_ops_ms")


def _window(record):
    """The recorder and the harness's window in it, or (None, None)."""
    try:
        from mpi4dl_tpu.obs.spans import recorder
    except ImportError:
        return None, None
    rec = recorder()
    return rec, rec.last_run(len(record["spans"].get("dispatch") or ()))


def _bandwidth(record):
    """The chip's memory bandwidth: the record's, or ``peaks.json``'s row for
    the device the run is on (the harness's record carries the bf16 peak
    alone)."""
    given = record["peaks"].get("hbm_bytes_per_s")
    if given:
        return given
    import jax

    from perfbench.catalog import Catalog
    return Catalog().peak(jax.devices()[0].device_kind, "hbm_bytes_per_s")


def experts_work(record, *, hidden, ffn, held, layers):
    """The grouped SwiGLU over the rows the step's counter read
    (``expert_rows``: rows routed to held experts, summed over the expert
    layers; the median step of the window).

    Operations: a row takes 3 x hidden x ffn multiply-accumulates forward
    (W1, W3, W2).  The step runs the forward twice (per-cell remat recomputes
    it in the backward sweep) and the backward costs two products for each
    forward one (by the rows and by the weights): 4 x 2 x 3 x hidden x ffn
    FLOPs a row.

    Bytes, bf16, each product reading its rows and its weights once and
    writing its result once: a row moves 2 x hidden + 2 x ffn (read for W1
    and W3, and their results) + ffn + hidden (W2) values, and each of the
    ``layers`` expert layers' held weights are 3 x held x hidden x ffn
    values; the same factor of 4 for the four passes."""
    rec, run = _window(record)
    if run is None:
        return None
    steps = [s.attrs for s in rec.closed("step", within=run)
             if "expert_rows" in s.attrs and s.attrs.get("expert_assignments")]
    if not steps:
        return None
    rows = statistics.median(a["expert_rows"] for a in steps)
    passes = 4
    flops = passes * 2 * 3 * hidden * ffn * rows
    values = rows * (3 * hidden + 3 * ffn) + layers * 3 * held * hidden * ffn
    return flops, passes * 2 * values


def attention_work(record, *, seq_len, head_dim, heads, kv_heads, layers):
    """Causal attention over the scores the reference counted
    (``forward_macs_per_img["attn_scores"]``: the causal half, for q k^T and
    for p v, all attention layers) times the step's samples (the ``run``
    span's ``global_batch``).

    Operations: the forward is those two products; the step runs it twice
    (per-cell remat) and the backward is five products of the same size
    (s, dp, dq, dk, dv) where the forward has two: (2 + 2.5) x 2 FLOPs a
    counted multiply-accumulate.

    Bytes, bf16: a blocked attention reads q, k, v and writes o once a pass
    (three passes) and never the scores: seq_len x head_dim values for each
    query head twice (q, o) and each key-value head twice (k, v), for each
    of the ``layers`` attention layers and each sample.  Far under the operations' time at any length that needs
    blocking: the roofline is the bf16 peak's."""
    macs_img = record["model"]["forward_macs_per_img"].get("attn_scores")
    rec, run = _window(record)
    if not macs_img or run is None or not run.attrs.get("global_batch"):
        return None
    batch = run.attrs["global_batch"]
    values = batch * layers * seq_len * head_dim * 2 * (heads + kv_heads)
    return (2 + 2.5) * 2 * macs_img * batch, 3 * 2 * values


def read(record, work, params, pattern=None, scope=None):
    seconds = _ops.op_seconds(record, pattern, scope)
    flops_peak = record["peaks"].get("bf16_flops")
    if seconds is None or not flops_peak:
        return None
    done = {"experts": experts_work, "attention": attention_work}[work](
        record, **params)
    if done is None:
        return None
    flops, nbytes = done
    least = max(flops / flops_peak, nbytes / _bandwidth(record))
    return 100.0 * least / seconds
