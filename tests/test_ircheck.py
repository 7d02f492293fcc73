"""Tests for the IR-level shard-flow verifier (ISSUE 16 tentpole).

Every finding kind in ``analysis/ircheck``'s classification has a violating
fixture here — hand-built jaxprs traced through ``compat.shard_map`` on the
8-CPU virtual mesh for the replication-flow / collective-matching kinds,
hand-written scheduled-HLO modules for the donation / async / Pallas-alias
kinds — plus the matching clean fixtures proving the checks do not fire on
well-formed programs.  The localization tests inject violations into a real
engine family and assert the finding names the owning ``obs.scope``
(the acceptance criterion: a bad perm in the halo exchange must say
``halo_exchange_spw``, not point at the whole program).
"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi4dl_tpu.analysis.ircheck import (
    FINDING_KINDS,
    Finding,
    check_hlo,
    check_jaxpr,
    finding_counts,
)
from mpi4dl_tpu.analysis.ircheck.collectives import (
    _group_problems,
    _perm_problems,
    hlo_collective_findings,
    jaxpr_collective_findings,
    participant_count,
)
from mpi4dl_tpu.analysis.ircheck.donation import (
    donation_findings,
    parse_input_output_alias,
)
from mpi4dl_tpu.analysis.ircheck.asyncsafe import async_findings


def _mesh():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("sph", "spw"))


def _smap(body, mesh, in_specs, out_specs):
    from mpi4dl_tpu.compat import shard_map

    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _kinds(findings):
    return sorted({f.kind for f in findings})


# ---------------------------------------------------------------------------
# jaxpr level: replication flow (wasted-wire / divergent-collective)
# ---------------------------------------------------------------------------


def test_wasted_wire_psum_of_replicated(devices8):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh()

    def step(x):
        def body(x):
            with jax.named_scope("junction_reduce"):
                # jnp.asarray(...) is a closed constant — replicated along
                # every manual axis — so this psum moves wire for a value
                # every shard already holds.
                return x * lax.psum(jnp.asarray(3.0, jnp.float32), "spw")
        return _smap(body, mesh, P("sph"), P("sph"))(x)

    fs = check_jaxpr(jax.make_jaxpr(step)(jnp.zeros((8, 4))))
    ww = [f for f in fs if f.kind == "wasted-wire"]
    assert ww, fs
    assert any("junction_reduce" in f.scope for f in ww), ww
    assert all(f.bytes > 0 for f in ww), ww


def test_clean_reduce_of_varying_value(devices8):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh()

    def step(x):
        def body(x):
            # x is sharded over "sph": the psum genuinely combines shards.
            return lax.psum(x, "sph")
        return _smap(body, mesh, P("sph"), P(None))(x)

    assert check_jaxpr(jax.make_jaxpr(step)(jnp.zeros((8, 4)))) == []


def test_divergent_collective_under_cond(devices8):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh()

    def step(x):
        def body(x):
            pred = lax.axis_index("sph") > 0

            def taken(v):
                with jax.named_scope("junction_gather"):
                    return lax.psum(v, "sph")

            return lax.cond(pred, taken, lambda v: v, x)
        return _smap(body, mesh, P("sph"), P("sph"))(x)

    fs = check_jaxpr(jax.make_jaxpr(step)(jnp.zeros((8, 4))))
    div = [f for f in fs if f.kind == "divergent-collective"]
    assert div, fs
    # The finding carries the owning obs.scope, not the cond's position —
    # jax resets name stacks in branch traces, so this exercises the
    # interpreter's scope re-prefixing.
    assert any("junction_gather" in f.scope for f in div), div


def test_collective_on_axis_predicate_is_replicated_along_is_clean(devices8):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh()

    def step(x):
        def body(x):
            # Predicate varies along "sph" but is UNIFORM along "spw": a psum
            # over "spw" cannot deadlock (all "spw"-peers agree on the branch).
            pred = lax.axis_index("sph") > 0
            return lax.cond(pred, lambda v: lax.psum(v, "spw"),
                            lambda v: v, x)
        return _smap(body, mesh, P(("sph", "spw")), P(("sph", "spw")))(x)

    fs = check_jaxpr(jax.make_jaxpr(step)(jnp.zeros((8, 4))))
    assert [f for f in fs if f.kind == "divergent-collective"] == [], fs


def test_divergent_collective_in_while_loop(devices8):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh()

    def step(x):
        def body(x):
            # Trip count varies along "sph": "sph"-peers disagree on how many
            # psums over "sph" execute — the deadlock class.
            trips = lax.axis_index("sph")

            def loop_body(carry):
                i, v = carry
                return i + 1, lax.psum(v, "sph")

            _, out = lax.while_loop(lambda c: c[0] < trips,
                                    loop_body, (jnp.int32(0), x))
            return out
        return _smap(body, mesh, P("sph"), P("sph"))(x)

    fs = check_jaxpr(jax.make_jaxpr(step)(jnp.zeros((8, 4))))
    assert any(f.kind == "divergent-collective" for f in fs), fs


def test_scan_carry_fixpoint_clean_ring(devices8):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh()

    def step(x):
        def body(x):
            def hop(c, _):
                return lax.ppermute(c, "sph", [(i, (i + 1) % 4)
                                             for i in range(4)]), None

            c, _ = lax.scan(hop, x, None, length=3)
            return c
        return _smap(body, mesh, P("sph"), P("sph"))(x)

    assert check_jaxpr(jax.make_jaxpr(step)(jnp.zeros((8, 4)))) == []


# ---------------------------------------------------------------------------
# jaxpr level: collective matching (nonbijective-perm / replica groups)
# ---------------------------------------------------------------------------


def test_nonbijective_perm_in_scan_names_scope(devices8):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh()

    def step(x):
        def body(x):
            def hop(c, _):
                with jax.named_scope("hop"):
                    # duplicate source 0 AND destination 9 beyond axis
                    # size 4 — both perm proofs at once.
                    c = lax.ppermute(  # analysis: ok(collective-axis)
                        c, "sph", [(0, 1), (0, 2), (2, 9)])
                return c, None

            with jax.named_scope("ring"):
                c, _ = lax.scan(hop, x, None, length=2)
            return c
        return _smap(body, mesh, P("sph"), P("sph"))(x)

    fs = check_jaxpr(jax.make_jaxpr(step)(jnp.zeros((8, 4))))
    perms = [f for f in fs if f.kind == "nonbijective-perm"]
    msgs = " | ".join(f.message for f in perms)
    assert "duplicate source" in msgs and "out of range" in msgs, perms
    # scope joins the enclosing scan's stack with the body's relative stack
    assert all("ring" in f.scope and "hop" in f.scope for f in perms), perms


def _fake_eqn(prim, params, source_info=None):
    return types.SimpleNamespace(
        primitive=types.SimpleNamespace(name=prim),
        params=params, invars=[], outvars=[], source_info=source_info,
    )


def test_mismatched_replica_groups_jaxpr_level():
    # jax validates axis_index_groups eagerly at trace time, so the
    # violating jaxpr is duck-typed — the walker reads only
    # primitive.name/params/source_info, which is exactly what a malformed
    # hand-built jaxpr (the case this check exists for) would present.
    fake_mesh = types.SimpleNamespace(axis_names=("sph",), shape={"sph": 4})
    body = types.SimpleNamespace(eqns=[
        _fake_eqn("psum", {"axes": ("sph",),
                           "axis_index_groups": [[0, 1], [1, 2]]}),
    ])
    sm = _fake_eqn("shard_map", {
        "mesh": fake_mesh, "auto": frozenset(), "in_names": (),
        "jaxpr": body,
    })
    fs = jaxpr_collective_findings(types.SimpleNamespace(eqns=[sm]))
    assert _kinds(fs) == ["mismatched-replica-groups"], fs
    msgs = " | ".join(f.message for f in fs)
    assert "more than one group" in msgs, fs
    assert "cover" in msgs or "appear" in msgs, fs


def test_perm_and_group_problem_proofs():
    assert _perm_problems([(0, 1), (1, 0)], 2) == []
    assert any("duplicate destination" in p
               for p in _perm_problems([(0, 1), (2, 1)], 4))
    # size unknown: range check skipped, injectivity still proven
    assert _perm_problems([(0, 9)], None) == []
    assert any("out of range" in p for p in _perm_problems([(0, 9)], 4))

    assert _group_problems([[0, 1], [2, 3]], 4) == []
    assert any("unequal" in p for p in _group_problems([[0], [1, 2]], 3))
    assert any("cover" in p for p in _group_problems([[0, 1]], 4))
    assert any("out of range" in p for p in _group_problems([[0, 7]], 4))


# ---------------------------------------------------------------------------
# compiled-HLO level: collective matching
# ---------------------------------------------------------------------------

_HLO_BAD_COLLECTIVES = """\
HloModule bad_coll, is_scheduled=true, num_partitions=4

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %cp = f32[8]{0} collective-permute(%p0), source_target_pairs={{0,1},{0,2},{3,7}}, metadata={op_name="jit(step)/shard_map/halo_exchange_spw/cp"}
  %ar = f32[8]{0} all-reduce(%cp), replica_groups={{0,1},{1,2,3}}, to_apply=%add, metadata={op_name="jit(step)/shard_map/grad_reduce/ar"}
  ROOT %out = f32[8]{0} add(%cp, %ar)
}
"""


def test_hlo_nonbijective_perm_and_groups():
    assert participant_count(_HLO_BAD_COLLECTIVES) == 4
    fs = hlo_collective_findings(_HLO_BAD_COLLECTIVES)
    perms = [f for f in fs if f.kind == "nonbijective-perm"]
    groups = [f for f in fs if f.kind == "mismatched-replica-groups"]
    assert perms and groups, fs
    pmsgs = " | ".join(f.message for f in perms)
    assert "duplicate source" in pmsgs and "out of range" in pmsgs, perms
    assert any("halo_exchange_spw" in f.scope for f in perms), perms
    gmsgs = " | ".join(f.message for f in groups)
    assert "unequal" in gmsgs or "more than one group" in gmsgs, groups
    assert any("grad_reduce" in f.scope for f in groups), groups


def test_hlo_clean_collectives():
    clean = _HLO_BAD_COLLECTIVES.replace(
        "{{0,1},{0,2},{3,7}}", "{{0,1},{1,0}}"
    ).replace("{{0,1},{1,2,3}}", "{{0,1},{2,3}}")
    assert hlo_collective_findings(clean) == []


# ---------------------------------------------------------------------------
# compiled-HLO level: donation safety
# ---------------------------------------------------------------------------

_HLO_DONATION = """\
HloModule donate, is_scheduled=true, input_output_alias={ {0}: (0, {}, must-alias), {1}: (0, {}, may-alias) }, num_partitions=2

ENTRY %main (p0: f32[128], p1: f32[128]) -> (f32[128], f32[128]) {
  %p0 = f32[128]{0} parameter(0)
  %p1 = f32[128]{0} parameter(1)
  %add = f32[128]{0} add(%p0, %p1), metadata={op_name="jit(step)/optimizer_update/add"}
  %mul = f32[128]{0} multiply(%p0, %add), metadata={op_name="jit(step)/late_reader/mul"}
  ROOT %out = (f32[128]{0}, f32[128]{0}) tuple(%add, %mul)
}
"""


def test_parse_input_output_alias():
    aliases = parse_input_output_alias(_HLO_DONATION)
    assert aliases == [
        {"output": (0,), "param": 0, "param_index": (), "kind": "must-alias"},
        {"output": (1,), "param": 0, "param_index": (), "kind": "may-alias"},
    ]
    assert parse_input_output_alias("HloModule m, is_scheduled=true\n") == []


def test_read_after_donate_and_double_donation():
    fs = donation_findings(_HLO_DONATION)
    assert _kinds(fs) == ["double-donation", "read-after-donate"], fs
    rad = [f for f in fs if f.kind == "read-after-donate"]
    # %mul reads donated %p0 after %add (the aliased output) was written —
    # and the finding names the reader's owning scope.
    assert any("late_reader" in f.scope for f in rad), rad
    assert any("%mul" in f.message and "%add" in f.message for f in rad), rad


def test_donation_identity_passthrough_is_clean():
    # Output 0 IS the donated parameter (state passed through unchanged):
    # later reads see unchanged bytes — not a violation.
    hlo = """\
HloModule passthrough, is_scheduled=true, input_output_alias={ {0}: (0, {}) }

ENTRY %main (p0: f32[16]) -> (f32[16], f32[16]) {
  %p0 = f32[16]{0} parameter(0)
  %sq = f32[16]{0} multiply(%p0, %p0)
  ROOT %out = (f32[16]{0}, f32[16]{0}) tuple(%p0, %sq)
}
"""
    assert donation_findings(hlo) == []


def test_malformed_carry_alias():
    hlo = """\
HloModule carry, is_scheduled=true

%body (bp: (f32[8], s32[])) -> (f32[16], s32[]) {
  %bp = (f32[8]{0}, s32[]) parameter(0)
  %g0 = f32[8]{0} get-tuple-element(%bp), index=0
  %g1 = s32[] get-tuple-element(%bp), index=1
  %big = f32[16]{0} concatenate(%g0, %g0), dimensions={0}
  ROOT %bt = (f32[16]{0}, s32[]) tuple(%big, %g1)
}

%cond (cp: (f32[8], s32[])) -> pred[] {
  %cp = (f32[8]{0}, s32[]) parameter(0)
  %i = s32[] get-tuple-element(%cp), index=1
  %lim = s32[] constant(4)
  ROOT %lt = pred[] compare(%i, %lim), direction=LT
}

ENTRY %main (p0: (f32[8], s32[])) -> (f32[8], s32[]) {
  %p0 = (f32[8]{0}, s32[]) parameter(0)
  ROOT %w = (f32[8]{0}, s32[]) while(%p0), condition=%cond, body=%body, metadata={op_name="jit(step)/ring_scan/while"}
}
"""
    fs = donation_findings(hlo)
    assert _kinds(fs) == ["malformed-carry-alias"], fs
    assert any("ring_scan" in f.scope for f in fs), fs
    assert any("body root" in f.message for f in fs), fs


# ---------------------------------------------------------------------------
# compiled-HLO level: async well-formedness
# ---------------------------------------------------------------------------

_HLO_UNPAIRED = """\
HloModule unpaired, is_scheduled=true

ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %ags = (f32[64]{0}, f32[128]{0}) all-gather-start(%p0), dimensions={0}, metadata={op_name="jit(step)/stage_lineup/ag"}
  %orphan = f32[64]{0} collective-permute-done(%p0), metadata={op_name="jit(step)/halo_exchange_spw/cpd"}
  ROOT %r = f32[64]{0} add(%orphan, %p0)
}
"""


def test_unpaired_async_start_and_orphan_done():
    fs = async_findings(_HLO_UNPAIRED)
    assert _kinds(fs) == ["unpaired-async"], fs
    msgs = " | ".join(f.message for f in fs)
    assert "never awaited" in msgs, fs
    assert "done without start" in msgs, fs
    assert any("stage_lineup" in f.scope for f in fs), fs
    assert any("halo_exchange_spw" in f.scope for f in fs), fs


def test_double_done_is_unpaired():
    hlo = """\
HloModule twodones, is_scheduled=true

ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %cps = (f32[64]{0}, f32[64]{0}) collective-permute-start(%p0), source_target_pairs={{0,1},{1,0}}
  %d1 = f32[64]{0} collective-permute-done(%cps)
  %d2 = f32[64]{0} collective-permute-done(%cps)
  ROOT %r = f32[64]{0} add(%d1, %d2)
}
"""
    fs = async_findings(hlo)
    assert _kinds(fs) == ["unpaired-async"], fs
    assert any("2 dones" in f.message for f in fs), fs


_HLO_RACE = """\
HloModule race, is_scheduled=true

ENTRY %main (p0: f32[64], p1: f32[8]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %c0 = s32[] constant(0)
  %cps = (f32[64]{0}, f32[64]{0}) collective-permute-start(%p0), source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(step)/halo_exchange_spw/cp"}
  %gte = f32[64]{0} get-tuple-element(%cps), index=1
  %leak = f32[64]{0} copy(%gte), metadata={op_name="jit(step)/cell00/leak"}
  %dus = f32[64]{0} dynamic-update-slice(%p0, %p1, %c0), metadata={op_name="jit(step)/cell00/dus"}
  %cpd = f32[64]{0} collective-permute-done(%cps)
  ROOT %r = f32[64]{0} add(%cpd, %dus)
}
"""


def test_async_dma_race_consume_and_overwrite():
    fs = async_findings(_HLO_RACE)
    assert _kinds(fs) == ["async-dma-race"], fs
    msgs = " | ".join(f.message for f in fs)
    # %leak consumes the in-flight start tuple inside the window...
    assert "consumes the in-flight" in msgs, fs
    # ...and %dus overwrites the DMA source buffer (%p0) mid-transfer.
    assert "DMA source overwritten" in msgs, fs
    assert all("cell00" in f.scope for f in fs), fs


def test_async_clean_pair_with_unrelated_compute():
    hlo = """\
HloModule cleanasync, is_scheduled=true

ENTRY %main (p0: f32[64], p1: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %p1 = f32[64]{0} parameter(1)
  %cps = (f32[64]{0}, f32[64]{0}) collective-permute-start(%p0), source_target_pairs={{0,1},{1,0}}
  %hide = f32[64]{0} multiply(%p1, %p1)
  %cpd = f32[64]{0} collective-permute-done(%cps)
  ROOT %r = f32[64]{0} add(%cpd, %hide)
}
"""
    assert async_findings(hlo) == []


def test_async_chain_resolves_through_update_glue_and_wrapper():
    # Nested async-update glue on a generic async-start wrapping a
    # collective computation: the done resolves through the chain (no
    # unpaired-async), matching obs/overlap.py's ledger walk.
    hlo = """\
HloModule glue, is_scheduled=true

%wrapped (wp: f32[32]) -> f32[32] {
  %wp = f32[32]{0} parameter(0)
  ROOT %ar = f32[32]{0} all-reduce(%wp), to_apply=%add
}

ENTRY %main (p0: f32[32]) -> f32[32] {
  %p0 = f32[32]{0} parameter(0)
  %as = ((f32[32]{0}), f32[32]{0}, u32[]) async-start(%p0), calls=%wrapped
  %u1 = ((f32[32]{0}), f32[32]{0}, u32[]) async-update(%as)
  %u2 = ((f32[32]{0}), f32[32]{0}, u32[]) async-update(%u1)
  %ad = f32[32]{0} async-done(%u2), calls=%wrapped
  ROOT %r = f32[32]{0} add(%ad, %p0)
}
"""
    assert async_findings(hlo) == []


def test_pallas_alias_contracts():
    hlo = """\
HloModule pallas, is_scheduled=true

ENTRY %main (p0: f32[64], p1: f32[32]) -> (f32[64], f32[32]) {
  %p0 = f32[64]{0} parameter(0)
  %p1 = f32[32]{0} parameter(1)
  %cc = (f32[64]{0}, f32[32]{0}) custom-call(%p0, %p1), custom_call_target="tpu_custom_call", output_to_operand_aliasing={{0}: (0, {}), {1}: (0, {})}, metadata={op_name="jit(step)/pallas_conv/cc"}
  %cc2 = f32[64]{0} custom-call(%p0, %p1), custom_call_target="tpu_custom_call", output_to_operand_aliasing={{}: (5, {})}, metadata={op_name="jit(step)/pallas_conv/cc2"}
  %cc3 = f32[64]{0} custom-call(%p1), custom_call_target="tpu_custom_call", output_to_operand_aliasing={{}: (0, {})}, metadata={op_name="jit(step)/pallas_attention/cc3"}
  ROOT %out = (f32[64]{0}, f32[32]{0}) tuple(%cc, %p1)
}
"""
    fs = async_findings(hlo)
    assert _kinds(fs) == ["pallas-alias"], fs
    msgs = " | ".join(f.message for f in fs)
    assert "double alias" in msgs, fs          # %cc aliases operand 0 twice
    assert "only 2 operand(s)" in msgs, fs     # %cc2 operand 5 out of range
    assert "!=" in msgs, fs                    # %cc3 f32[64] vs f32[32]
    assert all("pallas" in f.scope for f in fs), fs


def test_pallas_alias_wellformed_is_clean():
    hlo = """\
HloModule pallasok, is_scheduled=true

ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  ROOT %cc = f32[64]{0} custom-call(%p0), custom_call_target="tpu_custom_call", output_to_operand_aliasing={{}: (0, {})}
}
"""
    assert async_findings(hlo) == []


# ---------------------------------------------------------------------------
# check_hlo / finding_counts composition
# ---------------------------------------------------------------------------


def test_check_hlo_composes_and_counts():
    fs = check_hlo(_HLO_DONATION)
    counts = finding_counts(fs)
    assert counts == {"double-donation": 1, "read-after-donate": 1}, counts
    assert all(k in FINDING_KINDS for k in counts)
    assert finding_counts([]) == {}


def test_finding_render_and_baseline_key():
    f = Finding(kind="wasted-wire", scope="loss_reduce", message="m",
                family="sp", bytes=16)
    assert f.render() == "sp:loss_reduce: [wasted-wire] m (~16 bytes)"
    assert f.baseline_key == ("wasted-wire", "sp", "loss_reduce", "m")
    assert Finding(kind="x", scope="", message="m").render() == \
        "<unscoped>: [x] m"


# ---------------------------------------------------------------------------
# localization on a real engine family (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_engine_families_prove_clean(devices8):
    import jax

    from mpi4dl_tpu.analysis.contracts.engines import build_engine

    for family in ("lp", "sp"):
        step, args = build_engine(family)
        fs = check_jaxpr(jax.make_jaxpr(step)(*args), family=family)
        assert fs == [], f"{family}: {[f.render() for f in fs]}"


def test_injected_bad_perm_names_halo_scope(devices8, monkeypatch):
    """A non-bijective perm smuggled into the halo exchange must be
    reported as ``nonbijective-perm`` at the owning ``halo_exchange_spw``
    scope — through the real sp engine's scan/shard_map nesting."""
    import jax
    from jax import lax

    import mpi4dl_tpu.ops.halo as halo
    from mpi4dl_tpu.analysis.contracts.engines import build_engine

    def bad_shift(x, axis_name, n, step=1):
        perm = [(i, i + step) for i in range(n - step)]
        return lax.ppermute(x, axis_name, perm + [(0, n + 3)])

    monkeypatch.setattr(halo, "_shift_from_prev", bad_shift)
    step, args = build_engine("sp")
    fs = check_jaxpr(jax.make_jaxpr(step)(*args), family="sp")
    perms = [f for f in fs if f.kind == "nonbijective-perm"]
    assert perms, [f.render() for f in fs]
    for f in perms:
        assert "halo_exchange_spw" in f.scope, f.render()
        assert ("duplicate" in f.message or "out of range" in f.message)
    # localization: nothing else drifted
    assert all(f.kind == "nonbijective-perm" for f in fs), \
        [f.render() for f in fs]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _cli(argv):
    from mpi4dl_tpu.analysis.ircheck.__main__ import main

    return main(argv)


def test_ircheck_cli_unknown_family(capsys):
    assert _cli(["--families", "nope"]) == 2
    assert "unknown engine" in capsys.readouterr().err


def test_ircheck_cli_quant_off_rejected(capsys):
    assert _cli(["--families", "lp", "--quant", "off"]) == 2
    assert "drop the flag" in capsys.readouterr().err


def test_ircheck_cli_json_baseline_sarif(tmp_path, devices8, monkeypatch,
                                         capsys):
    import mpi4dl_tpu.analysis.ircheck as ircheck_pkg

    fake = [
        Finding(kind="wasted-wire", scope="loss_reduce",
                message="synthetic", family="lp", bytes=4),
        Finding(kind="unpaired-async", scope="halo_exchange_spw",
                message="other", family="lp"),
    ]
    monkeypatch.setattr(ircheck_pkg, "check_family",
                        lambda family, quant=None, build=None: list(fake))

    out = tmp_path / "findings.json"
    sarif = tmp_path / "findings.sarif"
    rc = _cli(["--families", "lp", "--json", "--out", str(out),
               "--sarif", str(sarif)])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert {r["kind"] for r in payload["findings"]} == \
        {"wasted-wire", "unpaired-async"}
    assert json.loads(out.read_text()) == payload

    log = json.loads(sarif.read_text())
    assert log["version"] == "2.1.0"
    results = log["runs"][0]["results"]
    assert {r["ruleId"] for r in results} == \
        {"ircheck/wasted-wire", "ircheck/unpaired-async"}
    assert any("loss_reduce" in r["message"]["text"] for r in results)

    # baseline filtering: accept one of the two, exit reflects the rest
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps([
        {"kind": "wasted-wire", "family": "lp", "scope": "loss_reduce",
         "message": "synthetic"},
    ]))
    rc = _cli(["--families", "lp", "--json", "--baseline", str(base)])
    assert rc == 1
    rows = json.loads(capsys.readouterr().out)["findings"]
    assert [r["kind"] for r in rows] == ["unpaired-async"]

    base.write_text(json.dumps([
        {"kind": f.kind, "family": f.family, "scope": f.scope,
         "message": f.message} for f in fake
    ]))
    assert _cli(["--families", "lp", "--baseline", str(base)]) == 0


def test_analysis_cli_dispatches_ircheck(capsys):
    from mpi4dl_tpu.analysis.__main__ import main

    assert main(["ircheck", "--families", "nope"]) == 2
    assert "unknown engine" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# contract integration (schema 3's ircheck section)
# ---------------------------------------------------------------------------


def test_contract_diff_reports_ircheck_drift():
    from mpi4dl_tpu.analysis.contracts import (
        diff_contracts,
        render_drift_report,
    )

    base = {"schema": 3, "engine": "lp", "ircheck": {}}
    drifted = {"schema": 3, "engine": "lp",
               "ircheck": {"wasted-wire": 2, "unpaired-async": 1}}
    drifts = diff_contracts(base, drifted)
    assert {(d["kind"], d.get("finding")) for d in drifts} == {
        ("ircheck", "wasted-wire"), ("ircheck", "unpaired-async"),
    }
    report = render_drift_report("lp", drifts)
    assert "ircheck finding wasted-wire: count 0 -> 2" in report
    assert diff_contracts(base, {"schema": 3, "engine": "lp",
                                 "ircheck": {}}) == []
