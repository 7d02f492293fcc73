"""Sparse attention's share of its roofline, in %: the least time the chip
could take for the attention over the selected keys a step executed (the
larger of operations over the chip's bf16 peak and bytes over its memory
bandwidth, ``peaks.json``), over the device time of the instructions
``scope`` picks (``trace_ops_ms``'s sum).  None without a trace, a peak, the
instructions, the reference's count or the run's batch.
"""

import os

from perfbench.catalog import _load_module

_HERE = os.path.dirname(os.path.abspath(__file__))
_ops = _load_module(os.path.join(_HERE, "trace_ops_ms.py"),
                    "perfbench_reader_trace_ops_ms")
_kernel = _load_module(os.path.join(_HERE, "kernel_roofline.py"),
                       "perfbench_reader_kernel_roofline")


def sparse_attention_work(record, *, seq_len, heads, head_dim, width, layers):
    """Attention over the selected pairs the reference counted
    (``forward_macs_per_img["sparse_attn"]``: ``q k^T`` and ``p v`` at
    ``head_dim`` for every pair a query selected and every head, all layers)
    times the step's samples (the ``run`` span's ``global_batch``).

    Operations: the forward is those two products, counted ONCE, and the
    backward five products of the same size (s, dp, dq, dk, dv): (1 + 2.5) x
    2 FLOPs a counted multiply-accumulate.  A forward recomputed under
    per-cell remat is time the kernel spends beyond this work.

    Bytes, a pass forward and one backward: q, k, v and o of every head in
    bf16 (the kernel reads a key-value head for each query head it serves)
    and the selection's words, ``width`` int32 a query."""
    macs_img = record["model"]["forward_macs_per_img"].get("sparse_attn")
    rec, run = _kernel._window(record)
    if not macs_img or run is None or not run.attrs.get("global_batch"):
        return None
    batch = run.attrs["global_batch"]
    per_pass = batch * layers * seq_len * (4 * heads * head_dim * 2 + width * 4)
    return (1 + 2.5) * 2 * macs_img * batch, 2 * per_pass


def read(record, params, pattern=None, scope=None):
    seconds = _ops.op_seconds(record, pattern, scope)
    flops_peak = record["peaks"].get("bf16_flops")
    if seconds is None or not flops_peak:
        return None
    done = sparse_attention_work(record, **params)
    if done is None:
        return None
    flops, nbytes = done
    least = max(flops / flops_peak, nbytes / _kernel._bandwidth(record))
    return 100.0 * least / seconds
