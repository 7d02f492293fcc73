"""Latent attention's share of its roofline, in %: the least time the chip
could take for the causal attention a step executed, with keys wider than
values (the larger of operations over the chip's bf16 peak and bytes over its
memory bandwidth, ``peaks.json``), over the device time of the instructions
``scope`` or ``pattern`` picks (``trace_ops_ms``'s sum).  Beside
``kernel_roofline``'s ``attention_work``, which counts a head of one width:
here the five products of the backward pass run at their own widths.  None without a trace, a peak,
the instructions, the reference's count or the run's batch.
"""

import os

from perfbench.catalog import _load_module

_HERE = os.path.dirname(os.path.abspath(__file__))
_ops = _load_module(os.path.join(_HERE, "trace_ops_ms.py"),
                    "perfbench_reader_trace_ops_ms")
_kernel = _load_module(os.path.join(_HERE, "kernel_roofline.py"),
                       "perfbench_reader_kernel_roofline")


def latent_attention_work(record, *, seq_len, heads, qk_head_dim, v_head_dim,
                          layers):
    """Causal attention over the scores the reference counted
    (``forward_macs_per_img["attn_scores"]``: the causal half, ``q k^T`` at
    ``qk_head_dim`` and ``p v`` at ``v_head_dim`` a pair and head, all
    layers) times the step's samples (the ``run`` span's ``global_batch``).

    Operations: the forward is those two products, ``qk + v`` multiply-
    accumulates a pair and head; the step runs it twice (per-cell remat) and
    the backward is five products, three at the keys' width (s, dq, dk) and
    two at the values' (dp, dv): ``(2 + (3 qk + 2 v) / (qk + v)) x 2`` FLOPs
    a counted multiply-accumulate (832/320 at 192 and 128).

    Bytes, bf16: a blocked attention reads q, k, v and writes o once a pass
    (three passes) and never the scores: ``seq_len x (2 qk + 2 v)`` values a
    head, layer and sample.  Far under the operations' time at any length
    that needs blocking: the roofline is the bf16 peak's."""
    macs_img = record["model"]["forward_macs_per_img"].get("attn_scores")
    rec, run = _kernel._window(record)
    if not macs_img or run is None or not run.attrs.get("global_batch"):
        return None
    batch = run.attrs["global_batch"]
    backward = (3 * qk_head_dim + 2 * v_head_dim) / (qk_head_dim + v_head_dim)
    values = batch * layers * seq_len * heads * 2 * (qk_head_dim + v_head_dim)
    return (2 + backward) * 2 * macs_img * batch, 3 * 2 * values


def read(record, params, pattern=None, scope=None):
    seconds = _ops.op_seconds(record, pattern, scope)
    flops_peak = record["peaks"].get("bf16_flops")
    if seconds is None or not flops_peak:
        return None
    done = latent_attention_work(record, **params)
    if done is None:
        return None
    flops, nbytes = done
    least = max(flops / flops_peak, nbytes / _kernel._bandwidth(record))
    return 100.0 * least / seconds
