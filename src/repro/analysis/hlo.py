"""Tier 2 — the compiled-artifact auditor.

The Tier-1 lint reads *source*; this module reads what XLA actually
compiled and checks the repo's cross-backend averaging contracts on the
artifact itself, one place instead of per-test string matching:

* **collective count** — the stacked executor's Reduce and every
  inter-round sync lower to NONE where one device holds the members,
  EXACTLY ONE all-reduce on a flat multi-device member mesh (the
  flat-psum contract of ``averaging.psum_weighted_mean_members``) and
  EXACTLY TWO on the hierarchical ``('host', 'pod')`` mesh (intra-host
  then inter-host — ``hierarchical_psum_weighted_mean_members``); the
  epoch scan lowers to ZERO collectives (members are independent
  between syncs).
* **donation aliasing** — where a jit wrapper claims
  ``donate_argnames``, the compiled module must actually carry
  input→output aliases (``input_output_alias``); a silently dropped
  donation doubles the stacked-carry memory.
* **accumulator dtype** — averaging programs must do their adds /
  reductions / collectives in f32-or-wider even when the member leaves
  are bf16 (the PR 2 regression class).
* **compile budget** — a serving scorer's jit cache must hold at most
  one program per ladder bucket (the ``BucketedScorer`` discipline).

``audit_executor(cfg, backend=...)`` / ``audit_scorer(scorer)`` run the
full per-backend contract set; the ``check_*`` primitives audit any
lowered program. All checks return a ``Check`` (never raise) and
``AuditReport.raise_if_failed()`` / ``expect_ok()`` turn failures into
``ContractViolation`` — the tests' entry point.

The collective parser is shared with the roofline tooling
(``repro.launch.hlo_analysis``); this module adds the contract layer on
top of it.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import collective_stats

# `%name = f32[4,4]{1,0} add(...)` — dtype-prefixed op definitions
_OP_DEF_RE = re.compile(
    r"=\s*([a-z0-9]+)\[[0-9,]*\](?:\{[^}]*\})?\s*"
    r"([a-z][a-z0-9-]*(?:\.[0-9]+)?)\(")
# ops that accumulate/reduce values (the f32 floor applies to these;
# parameter/convert/broadcast/copy ops may carry any dtype)
_ACCUM_OPS = {"add", "subtract", "multiply", "divide", "reduce", "dot",
              "all-reduce", "reduce-scatter", "reduce-window"}
_SUB_F32 = {"bf16", "f16", "f8e4m3fn", "f8e5m2"}
# one `{out_index}: (param, {param_index}, may-alias)` entry per alias —
# the entry shape is unique to the input_output_alias header attribute
_ALIAS_ENTRY_RE = re.compile(r"\{[0-9, ]*\}:\s*\(([0-9]+),")


class ContractViolation(AssertionError):
    """A compiled artifact broke one of the averaging contracts."""


@dataclass
class Check:
    """One contract check on one program: name, pass/fail, detail."""
    name: str
    ok: bool
    detail: str = ""

    def __str__(self):
        mark = "ok " if self.ok else "FAIL"
        return f"[{mark}] {self.name}" + (f": {self.detail}"
                                          if self.detail else "")


@dataclass
class AuditReport:
    """The checks run against one program (or one backend's programs)."""
    program: str
    checks: List[Check] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return all(c.ok for c in self.checks)

    @property
    def failures(self) -> List[Check]:
        return [c for c in self.checks if not c.ok]

    def raise_if_failed(self) -> "AuditReport":
        if not self.ok:
            raise ContractViolation(
                f"{self.program}: "
                + "; ".join(str(c) for c in self.failures))
        return self

    def __str__(self):
        lines = [f"audit {self.program}:"]
        lines += [f"  {c}" for c in self.checks]
        return "\n".join(lines)


def _as_hlo_text(program) -> str:
    """Accept raw HLO text, a jax.stages.Lowered, or a Compiled."""
    if isinstance(program, str):
        return program
    if hasattr(program, "as_text") and not hasattr(program, "compile"):
        return program.as_text()            # Compiled
    if hasattr(program, "compile"):
        return program.compile().as_text()  # Lowered
    raise TypeError(f"cannot read HLO from {type(program).__name__}")


# ---------------------------------------------------------------------------
# Check primitives
# ---------------------------------------------------------------------------

def collective_counts(program) -> Dict[str, int]:
    """Collective-op counts by kind in the compiled module (the shared
    ``launch.hlo_analysis`` parser)."""
    return dict(collective_stats(_as_hlo_text(program)).count_by_kind)


def check_collectives(program, *, expect: Dict[str, int],
                      name: str = "collectives") -> Check:
    """The compiled module's collective counts must EQUAL ``expect``
    (``{}`` = zero collectives of any kind)."""
    got = collective_counts(program)
    ok = got == dict(expect)
    return Check(name, ok,
                 f"expected {dict(expect) or 'none'}, compiled has "
                 f"{got or 'none'}" if not ok else f"{got or 'none'}")


def check_one_all_reduce(program, *, name: str = "one-all-reduce") -> Check:
    """Exactly one all-reduce, nothing else — the flat-mesh Reduce/sync
    contract."""
    return check_collectives(program, expect={"all-reduce": 1}, name=name)


def check_two_all_reduces(program, *,
                          name: str = "two-all-reduces") -> Check:
    """Exactly two all-reduces, nothing else — the hierarchical
    ``('host', 'pod')`` Reduce/sync contract: one intra-host, one
    inter-host, independent of fleet size."""
    return check_collectives(program, expect={"all-reduce": 2}, name=name)


def check_no_collectives(program, *,
                         name: str = "zero-collectives") -> Check:
    """No collectives at all — the per-epoch Map contract."""
    return check_collectives(program, expect={}, name=name)


def ppermute_count(program) -> int:
    """Number of ``collective-permute`` ops (``lax.ppermute`` neighbor
    exchanges) in the compiled module — the gossip ring's currency."""
    return collective_counts(program).get("collective-permute", 0)


def check_gossip_sync(program, *, rounds: int,
                      name: str = "gossip-ring") -> Check:
    """The gossip-sync collective budget: EXACTLY ``2·rounds``
    collective-permutes (each unrolled mixing round is one right-shift +
    one left-shift neighbor exchange) and — because the expectation is an
    equality over ALL collective kinds — ZERO all-reduces: the
    decentralized sync never touches a global collective."""
    return check_collectives(
        program, expect={"collective-permute": 2 * rounds}, name=name)


def check_donation(program, *, min_aliases: int = 1,
                   name: str = "donation-aliased") -> Check:
    """The module header must carry ≥ ``min_aliases`` input→output
    aliases — proof the claimed ``donate_argnames`` actually landed
    (XLA drops donations it cannot use; a dropped epoch-carry donation
    doubles device memory silently)."""
    text = _as_hlo_text(program)
    n = 0
    if "input_output_alias" in text:
        n = len(_ALIAS_ENTRY_RE.findall(
            text.split("input_output_alias=", 1)[1].split("\n", 1)[0]))
    ok = n >= min_aliases
    return Check(name, ok,
                 f"{n} input->output aliases in the compiled module"
                 + ("" if ok else f" (expected >= {min_aliases} — was the "
                                  f"donated carry dropped?)"))


def check_accum_dtype(program, *, allow_param_dtypes: bool = True,
                      name: str = "f32-accumulation") -> Check:
    """No accumulation op (add/reduce/dot/all-reduce/...) may run below
    f32: a bf16 running sum rounds every add and drifts O(k·2^-8) off
    the true mean across k members."""
    text = _as_hlo_text(program)
    bad = []
    for dtype, op in _OP_DEF_RE.findall(text):
        base = op.split(".")[0]
        if base in _ACCUM_OPS and dtype in _SUB_F32:
            bad.append(f"{dtype} {base}")
    ok = not bad
    return Check(name, ok,
                 "all accumulation ops are f32+" if ok else
                 f"sub-f32 accumulation ops in compiled module: "
                 f"{sorted(set(bad))}")


def check_compile_budget(scorer, *, name: str = "compile-budget") -> Check:
    """A serving scorer's jit cache holds at most one compiled program
    per ladder bucket (duck-typed on ``compile_count()`` + ``ladder``,
    so it audits ``BucketedScorer`` without importing repro.serve)."""
    n = scorer.compile_count()
    budget = len(scorer.ladder.buckets)
    ok = n <= budget
    return Check(name, ok,
                 f"{n} compiled programs for {budget} buckets "
                 f"{tuple(scorer.ladder.buckets)}"
                 + ("" if ok else " — a dispatch escaped the pad ladder"))


# ---------------------------------------------------------------------------
# High-level audits: one call per backend / serving surface
# ---------------------------------------------------------------------------

def _tiny_inputs(cfg, k: int, batch_size: int, num_batches: int):
    img = ((cfg.image_size, cfg.image_size)
           if cfg.image_channels == 1 else
           (cfg.image_size, cfg.image_size, cfg.image_channels))
    xb = np.zeros((num_batches, k, batch_size) + img, np.float32)
    tb = np.zeros((num_batches, k, batch_size, cfg.num_classes), np.float32)
    mb = np.ones((num_batches, k), np.float32)
    return xb, tb, mb


def audit_executor(cfg, backend: str, *, mesh=None, k: int = 4,
                   batch_size: int = 8, num_batches: int = 2,
                   key=None, gossip_rounds: Optional[int] = None
                   ) -> List[AuditReport]:
    """Lower the named backend's actual programs and run its contract
    set. Returns one ``AuditReport`` per audited program; none raises —
    assert ``all(r.ok for r in reports)`` or call ``raise_if_failed()``.

    * ``"sequential"`` — the host Reduce (``average_models`` /
      ``average_trees``): f32 accumulation on bf16 members, zero
      collectives.
    * ``"stacked"`` / ``"mesh"`` — the stacked executor's programs on
      its member mesh (``mesh``, else the backend's default: one device
      for ``"stacked"``, every device for ``"mesh"``): ``_sync`` and
      ``_reduce`` hold the member mesh's collective budget — none where
      one device holds the members, ONE all-reduce on a flat multi-device
      mesh, TWO on the hierarchical ``('host', 'pod')`` mesh — with f32
      accumulation (lowered on bf16 members); the donated
      ``_stacked_epoch`` holds zero collectives. With ``gossip_rounds=T``
      the audit ALSO lowers the gossip sync and pins its ring budget on a
      multi-device mesh: exactly ``2·T`` collective-permutes and zero
      global all-reduces (``check_gossip_sync``); on one device the ring
      is the member dim, with no collective.

    Report names are ``"<backend>/<program>"``.
    """
    from repro.core import executor
    from repro.models import cnn

    reports: List[AuditReport] = []

    if backend == "sequential":
        # the host Reduce behind average_models: average_trees over the
        # (cnn_params, beta) member trees — lowered on bf16 members so
        # the f32 up-cast must live in the PROGRAM, not the inputs
        from repro.core.averaging import average_trees
        F, C = cnn.feature_dim(cfg), cfg.num_classes
        params = cnn.init_params(cfg, jax.random.PRNGKey(0)
                                 if key is None else key)
        bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        members = [(bf16, jnp.zeros((F, C), jnp.bfloat16))
                   for _ in range(k)]
        lowered = jax.jit(average_trees).lower(members)
        rep = AuditReport("sequential/average_trees")
        rep.checks += [check_accum_dtype(lowered),
                       check_no_collectives(lowered)]
        reports.append(rep)
        return reports

    ex, programs = lower_stacked_programs(
        cfg, backend, mesh=mesh, k=k, batch_size=batch_size,
        num_batches=num_batches, key=key, gossip_rounds=gossip_rounds)
    mesh = ex.mesh
    devices = executor._member_devices(mesh)
    # the per-sync collective budget is a function of the member-mesh
    # topology: none on one device, one flat psum on ('pod',), the
    # staged intra-host → inter-host pair on ('host', 'pod')
    budget = (check_no_collectives if devices == 1 else
              check_two_all_reduces if "host" in mesh.shape
              else check_one_all_reduce)
    for name, lowered in programs.items():
        rep = AuditReport(f"{backend}/{name}")
        if name == "_stacked_epoch":
            rep.checks += [check_no_collectives(lowered),
                           check_donation(lowered)]
        else:
            ring = name == "_gossip" and devices > 1
            rep.checks += [check_gossip_sync(lowered, rounds=gossip_rounds)
                           if ring else budget(lowered),
                           check_accum_dtype(lowered)]
        reports.append(rep)
    return reports


def lower_stacked_programs(cfg, backend: str = "stacked", *, mesh=None,
                           k: int = 4, batch_size: int = 8,
                           num_batches: int = 2, key=None,
                           gossip_rounds: Optional[int] = None):
    """Lower the stacked executor's programs on its member mesh
    (``mesh``, else the default of ``backend``, ``"stacked"`` or
    ``"mesh"``) for k members, padded as a run pads them. Returns the
    executor and ``{name: Lowered}``: ``_sync`` and ``_reduce`` on bf16
    members (so an f32 up-cast must live in the program, not the
    inputs), the donated ``_stacked_epoch`` on tiny batches and, with
    ``gossip_rounds``, the ``_gossip`` sync."""
    from repro.core import elm, executor
    from repro.core.averaging import broadcast_member_dim
    from repro.models import cnn

    if backend not in ("stacked", "mesh"):
        raise ValueError(f"backend must be one of ('sequential', "
                         f"'stacked', 'mesh'), got {backend!r}")
    key = jax.random.PRNGKey(0) if key is None else key
    F, C = cnn.feature_dim(cfg), cfg.num_classes
    ex = executor.make_executor(backend, mesh=mesh)
    ex._begin(cfg, k)
    params = cnn.init_params(cfg, key)
    bf16_k = ex._put(broadcast_member_dim(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), params), ex._k_pad))
    w = ex._weights(None)

    def lower(program, *args, **kw):
        with jax.set_mesh(ex.mesh):
            return program.lower(*args, **kw)

    beta_k = ex._put(jnp.zeros((ex._k_pad, F, C), jnp.bfloat16))
    cur = ex._put(_tiny_inputs(cfg, ex._k_pad, batch_size, num_batches),
                  member_dim=1)
    programs = {
        "_sync": lower(executor._sync, bf16_k, w),
        "_reduce": lower(executor._reduce, (bf16_k, beta_k), w),
        "_stacked_epoch": lower(
            executor._stacked_epoch, cfg,
            ex._put(broadcast_member_dim(params, ex._k_pad)),
            ex._put(elm.zero_stats_stacked(ex._k_pad, F, C)), *cur,
            jnp.float32(0.0), solve_each_batch=True, use_pallas=False,
            masked=True)}
    if gossip_rounds is not None:
        if "host" in ex.mesh.shape:
            raise ValueError("gossip rides the flat 1-D 'pod' ring — the "
                             "hierarchical ('host', 'pod') mesh has none")
        programs["_gossip"] = lower(executor._gossip, bf16_k, w,
                                    rounds=gossip_rounds, publish=False)
    return ex, programs


def audit_average_step(*, mesh=None, weights: Optional[Sequence] = None,
                       k: int = 8, leaf_shape=(4, 3)) -> AuditReport:
    """Audit ``trainer.make_average_step``'s lowered program — the
    launcher/dry-run averaging event: with a mesh, one all-reduce; f32
    accumulation either way (lowered on bf16 members to prove the
    up-cast is in the program, not the input)."""
    from repro.core import trainer
    from repro.distributed import sharding as shd
    params = {"w": jnp.zeros((k,) + tuple(leaf_shape), jnp.bfloat16)}
    step = jax.jit(trainer.make_average_step(weights=weights, mesh=mesh))
    if mesh is not None:
        params = jax.device_put(
            params, shd.member_dim_shardings(params, mesh))
    lowered = step.lower(params)
    rep = AuditReport("trainer/make_average_step"
                      + ("@mesh" if mesh is not None else ""))
    rep.checks.append(check_accum_dtype(lowered))
    rep.checks.append(check_one_all_reduce(lowered) if mesh is not None
                      else check_no_collectives(lowered))
    return rep


def audit_scorer(scorer, *, warm: bool = False) -> AuditReport:
    """The serving contract on a live ``BucketedScorer``-like object:
    the jit-cache compile count stays within the ladder budget.
    ``warm=True`` first warms every bucket so the audit covers the full
    ladder rather than whatever traffic happened to arrive."""
    if warm:
        scorer.warmup()
    rep = AuditReport("serve/BucketedScorer")
    rep.checks.append(check_compile_budget(scorer))
    return rep
