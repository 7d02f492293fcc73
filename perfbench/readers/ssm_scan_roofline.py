"""A state-space scan's share of its roofline, in %: the least time the chip
could take for the recurrence a step executed, whatever implements it (the
larger of operations over the chip's bf16 peak and bytes over its memory
bandwidth, ``peaks.json``), over the device time of the instructions
``scope`` or ``pattern`` picks (``trace_ops_ms``'s sum).  None without a
trace, a peak, the instructions, the reference's count or the run's batch.
"""

import os

from perfbench.catalog import _load_module

_HERE = os.path.dirname(os.path.abspath(__file__))
_ops = _load_module(os.path.join(_HERE, "trace_ops_ms.py"),
                    "perfbench_reader_trace_ops_ms")
_kernel = _load_module(os.path.join(_HERE, "kernel_roofline.py"),
                       "perfbench_reader_kernel_roofline")


def ssm_scan_work(record, *, seq_len, heads, head_dim, state, layers):
    """The recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t`` over what the reference counted
    (``forward_macs_per_img["ssm_scan"]``: ``2 x state x head_dim x heads``
    multiply-accumulates a position and layer, the state's update and its
    read-out) times the step's samples (the ``run`` span's ``global_batch``).

    Operations: two FLOPs a counted multiply-accumulate, four passes: the
    step runs the forward twice (per-cell remat) and the backward costs two
    products for each forward one.  A chunked form does more arithmetic
    than this (its products inside a chunk) and a sequential one exactly
    this: the count is the recurrence's, not an implementation's.

    Bytes, bf16: a scan that keeps its state on the chip reads ``x`` (``heads
    x head_dim`` values a position), ``B`` and ``C`` (``state`` each) and
    ``dt`` (``heads``) and writes ``y`` (``heads x head_dim``) once a pass,
    for each of the ``layers`` state-space layers and each sample.  At a
    state of 128 the bytes' time is about twice the operations': the
    roofline is the memory bandwidth's."""
    macs_img = record["model"]["forward_macs_per_img"].get("ssm_scan")
    rec, run = _kernel._window(record)
    if not macs_img or run is None or not run.attrs.get("global_batch"):
        return None
    batch = run.attrs["global_batch"]
    passes = 4
    values = batch * layers * seq_len * (2 * heads * head_dim + 2 * state + heads)
    return passes * 2 * macs_img * batch, passes * 2 * values


def read(record, params, pattern=None, scope=None):
    seconds = _ops.op_seconds(record, pattern, scope)
    flops_peak = record["peaks"].get("bf16_flops")
    if seconds is None or not flops_peak:
        return None
    done = ssm_scan_work(record, **params)
    if done is None:
        return None
    flops, nbytes = done
    least = max(flops / flops_peak, nbytes / _kernel._bandwidth(record))
    return 100.0 * least / seconds
