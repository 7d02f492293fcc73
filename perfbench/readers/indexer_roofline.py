"""The sparse attention's indexer's share of its roofline, in %: the least
time the chip could take for the indexer's scores and its loss's gradient a
step executed (the larger of operations over the chip's bf16 peak and bytes
over its memory bandwidth, ``peaks.json``), over the device time of the
instructions ``scope`` picks (``trace_ops_ms``'s sum).  None without a trace,
a peak, the instructions, the reference's count or the run's batch.
"""

import os

from perfbench.catalog import _load_module

_HERE = os.path.dirname(os.path.abspath(__file__))
_ops = _load_module(os.path.join(_HERE, "trace_ops_ms.py"),
                    "perfbench_reader_trace_ops_ms")
_kernel = _load_module(os.path.join(_HERE, "kernel_roofline.py"),
                       "perfbench_reader_kernel_roofline")


def selected_pairs(seq_len, topk):
    """Keys selected in a sequence: ``min(topk, t + 1)`` a query."""
    full = min(topk, seq_len)
    return full * (full + 1) // 2 + (seq_len - full) * topk


def indexer_work(record, *, seq_len, heads, head_dim, topk, width, layers):
    """The indexer's scores over every causal pair the reference counted
    (``forward_macs_per_img["indexer_scores"]``: ``heads x head_dim`` a pair,
    all layers), once, and its loss's backward: two products of the same
    width (to its queries and to its key) over the pairs selected alone,
    times the step's samples (the ``run`` span's ``global_batch``); 2 FLOPs a
    multiply-accumulate.

    Bytes, once: its queries and key in bf16, its head weights in float32 and
    the selection's words, ``width`` int32 a query."""
    macs_img = record["model"]["forward_macs_per_img"].get("indexer_scores")
    rec, run = _kernel._window(record)
    if not macs_img or run is None or not run.attrs.get("global_batch"):
        return None
    batch = run.attrs["global_batch"]
    backward = 2 * selected_pairs(seq_len, topk) * heads * head_dim * layers
    nbytes = batch * layers * seq_len * (
        (heads * head_dim + head_dim) * 2 + heads * 4 + width * 4)
    return 2 * (macs_img + backward) * batch, nbytes


def read(record, params, pattern=None, scope=None):
    seconds = _ops.op_seconds(record, pattern, scope)
    flops_peak = record["peaks"].get("bf16_flops")
    if seconds is None or not flops_peak:
        return None
    done = indexer_work(record, **params)
    if done is None:
        return None
    flops, nbytes = done
    least = max(flops / flops_peak, nbytes / _kernel._bandwidth(record))
    return 100.0 * least / seconds
