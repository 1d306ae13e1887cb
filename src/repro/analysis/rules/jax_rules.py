"""The JAX-specific lint rules behind the repo's cross-backend averaging
contracts. Each rule's docstring is its catalog entry (docs/analysis.md
is generated from these summaries); the ``# repro: allow(<rule>)``
suppression syntax and the contract each rule protects are documented
there too.
"""
from __future__ import annotations

import ast

from repro.analysis.astutil import (SUB_F32, dotted, is_partial_of,
                                    is_sub_f32, is_trace_wrapper_expr)
from repro.analysis.rules import rule

_NP_PREFIXES = ("np.", "numpy.")
# np.float32(...)-style dtype constructors build static constants — legal
# under trace, so they are exempt from np-in-traced
_NP_DTYPE_CTORS = {"float32", "float64", "float16", "bfloat16", "int8",
                   "int16", "int32", "int64", "uint8", "uint32", "uint64",
                   "bool_"}
_CONCRETIZING_METHODS = {"any", "all", "sum", "max", "min", "item",
                         "tolist"}
_ACCUM_CALLS = {"sum", "mean", "tensordot", "dot", "matmul", "einsum",
                "add", "cumsum", "average"}
_SEED_CTORS = {"default_rng", "PRNGKey", "RandomState", "seed"}


def _is_np_call(name):
    return name is not None and name.startswith(_NP_PREFIXES)


@rule("np-in-traced",
      "no numpy calls inside jitted/scanned/shard_mapped code — they "
      "concretize tracers (or silently constant-fold) and break the "
      "compiled program")
def np_in_traced(ctx):
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not ctx.traced.in_traced(node):
            continue
        name = dotted(node.func)
        if not _is_np_call(name):
            continue
        tail = name.split(".")[-1]
        if tail in _NP_DTYPE_CTORS:
            continue                      # static dtype constant
        if name.startswith(("np.random.", "numpy.random.")):
            continue                      # host-rng-or-clock's finding
        yield (node.lineno, node.col_offset,
               f"numpy call `{name}(...)` inside a traced function — use "
               f"jnp (or hoist the host computation out of the traced "
               f"path)")


@rule("host-concretization",
      "no float()/int()/bool()/.item()/.tolist() casts or Python "
      "branching on device values inside traced code — each forces a "
      "blocking device sync or a trace error")
def host_concretization(ctx):
    for node in ast.walk(ctx.tree):
        if not ctx.traced.in_traced(node):
            continue
        if isinstance(node, ast.Call):
            fname = dotted(node.func)
            if fname in ("float", "int", "bool") and node.args and \
                    not isinstance(node.args[0], ast.Constant):
                yield (node.lineno, node.col_offset,
                       f"`{fname}(...)` on a traced value concretizes the "
                       f"tracer — keep it a jnp scalar (or mark the "
                       f"argument static)")
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("item", "tolist") and not node.args:
                yield (node.lineno, node.col_offset,
                       f"`.{node.func.attr}()` inside a traced function "
                       f"blocks on the device — return the array and read "
                       f"it on the host")
        elif isinstance(node, (ast.If, ast.While)):
            bad = _concretizing_expr(node.test)
            if bad is not None:
                yield (node.lineno, node.col_offset,
                       f"Python `{type(node).__name__.lower()}` on "
                       f"`{bad}` inside a traced function branches on a "
                       f"tracer — use lax.cond/jnp.where")


def _concretizing_expr(test: ast.AST):
    """A subexpression of ``test`` that turns a device value into a
    Python bool (jnp call, or an .any()/.sum()-style reduction)."""
    for sub in ast.walk(test):
        if isinstance(sub, ast.Call):
            name = dotted(sub.func)
            if name is not None and name.startswith(("jnp.", "jax.numpy.")):
                return name
            if isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr in _CONCRETIZING_METHODS:
                return f".{sub.func.attr}()"
    return None


@rule("host-rng-or-clock",
      "no wall-clock or host-RNG calls inside traced functions — the "
      "value freezes at trace time, which silently breaks the "
      "bit-identical resume() contract")
def host_rng_or_clock(ctx):
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not ctx.traced.in_traced(node):
            continue
        name = dotted(node.func)
        if name is None:
            continue
        if name.startswith(("time.", "datetime.")) or name in (
                "perf_counter", "monotonic"):
            yield (node.lineno, node.col_offset,
                   f"wall-clock call `{name}(...)` inside a traced "
                   f"function is baked in at trace time — time on the "
                   f"host, around the dispatch")
        elif name.startswith(("random.", "np.random.", "numpy.random.")):
            yield (node.lineno, node.col_offset,
                   f"host RNG `{name}(...)` inside a traced function "
                   f"freezes one draw into the compiled program — use "
                   f"jax.random with an explicit key (the seed + i rule)")


_SPAN_CALLS = {"TraceAnnotation", "StepTraceAnnotation"}


@rule("host-span-in-traced",
      "no profiler host spans (TraceAnnotation/StepTraceAnnotation) "
      "inside traced functions — the span opens once, at trace time, "
      "and records nothing per step; scope device code with "
      "jax.named_scope and open spans around the dispatch")
def host_span_in_traced(ctx):
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not ctx.traced.in_traced(node):
            continue
        name = dotted(node.func)
        if name is not None and name.split(".")[-1] in _SPAN_CALLS:
            yield (node.lineno, node.col_offset,
                   f"host span `{name}(...)` inside a traced function "
                   f"fires once while tracing — use jax.named_scope "
                   f"inside, or open the span on the host around the "
                   f"call")


@rule("sub-f32-accum",
      "averaged/reduced trees must accumulate in f32 or wider — a bf16 "
      "running sum drifts O(k·2^-8) off the true mean (the PR 2 "
      "regression class)")
def sub_f32_accum(ctx):
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            name = dotted(node.func)
            tail = name.split(".")[-1] if name else ""
            if tail in _ACCUM_CALLS:
                for kw in node.keywords:
                    if kw.arg in ("dtype", "preferred_element_type") \
                            and is_sub_f32(kw.value):
                        yield (node.lineno, node.col_offset,
                               f"`{name}(..., {kw.arg}=<sub-f32>)` "
                               f"accumulates below f32 — average/reduce "
                               f"in f32, cast the RESULT back")
            if tail in ("psum", "pmean") and node.args \
                    and _is_sub_f32_cast(node.args[0]):
                yield (node.lineno, node.col_offset,
                       f"`{tail}` of a sub-f32 operand — the cross-member "
                       f"reduction must ride in f32 (cast after, not "
                       f"before)")
        elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            for side in (node.left, node.right):
                if _is_sub_f32_cast(side):
                    yield (node.lineno, node.col_offset,
                           "accumulating an `.astype(<sub-f32>)` operand "
                           "— sum in f32 and cast the final mean back")
        elif isinstance(node, ast.AugAssign) and \
                isinstance(node.op, ast.Add) and \
                _is_sub_f32_cast(node.value):
            yield (node.lineno, node.col_offset,
                   "`+=` of an `.astype(<sub-f32>)` operand — sum in f32 "
                   "and cast the final mean back")


def _is_sub_f32_cast(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "astype"
            and node.args and is_sub_f32(node.args[0]))


@rule("hardcoded-member-seed",
      "member rng streams derive from MapConfig.seed + member id — a "
      "literal base seed (`default_rng(1000 + i)`) silently diverges "
      "from the runner's streams the day the config seed changes")
def hardcoded_member_seed(ctx):
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        name = dotted(node.func)
        tail = name.split(".")[-1] if name else ""
        if tail not in _SEED_CTORS:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add) and \
                any(isinstance(s, ast.Constant) and isinstance(s.value, int)
                    for s in (arg.left, arg.right)):
            yield (node.lineno, node.col_offset,
                   f"`{tail}(<literal> + ...)` hardcodes a member seed "
                   f"base — derive it from MapConfig.member_seed(i) / "
                   f"plan.seed + i so every backend shares one rule")


@rule("missing-donate",
      "jitted functions that scan an epoch carry must donate it — "
      "without donate_argnums/donate_argnames XLA double-buffers the "
      "stacked params+stats every chunk")
def missing_donate(ctx):
    defs = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs[node.name] = node

    def has_scan(fn):
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call):
                d = dotted(sub.func)
                if d is not None and (d == "scan" or d.endswith("lax.scan")):
                    return True
        return False

    def jit_kwargs(expr):
        """keyword names of a jit/partial(jit, ...) wrapper expression."""
        if isinstance(expr, ast.Call):
            return {kw.arg for kw in expr.keywords}
        return set()

    def check(wrap_expr, target_fn, lineno, col):
        if target_fn is None or not has_scan(target_fn):
            return None
        kws = jit_kwargs(wrap_expr)
        if not kws & {"donate_argnums", "donate_argnames"}:
            return (lineno, col,
                    f"`{target_fn.name}` scans a carry but its jit "
                    f"wrapper donates nothing — pass donate_argnums/"
                    f"donate_argnames for the scan-carried buffers")
        return None

    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jit_expr(dec):
                    f = check(dec, node, node.lineno, node.col_offset)
                    if f:
                        yield f
        elif isinstance(node, ast.Call) and _is_jit_expr(node.func):
            # jax.jit(f, ...) or functools.partial(jax.jit, ...)(f)
            target = None
            if node.args:
                tname = dotted(node.args[0])
                target = defs.get(tname)
            wrap = node.func if isinstance(node.func, ast.Call) else node
            f = check(wrap, target, node.lineno, node.col_offset)
            if f:
                yield f


def _is_jit_expr(node: ast.AST) -> bool:
    name = dotted(node)
    if name in ("jax.jit", "jit"):
        return True
    if isinstance(node, ast.Call):
        if dotted(node.func) in ("jax.jit", "jit"):
            return True
        if is_partial_of(node, {"jax.jit", "jit"}):
            return True
    return False


@rule("bare-jit-in-serve",
      "the serving path compiles through BucketedScorer's pad ladder "
      "only — a bare jax.jit in repro.serve dodges the compile-budget "
      "discipline (one XLA program per bucket, assert_compile_budget)",
      paths=r"(^|/)repro/serve/")
def bare_jit_in_serve(ctx):
    for node in ast.walk(ctx.tree):
        name = dotted(node)
        if isinstance(node, (ast.Attribute, ast.Name)) and \
                name in ("jax.jit", "jit"):
            yield (node.lineno, node.col_offset,
                   "bare `jax.jit` in repro.serve — every serving "
                   "dispatch must go through BucketedScorer so "
                   "compile_count()/assert_compile_budget() see it")


@rule("unregistered-reduce-strategy",
      "`strategy=<string>` must name a registered ReduceStrategy — an "
      "unregistered literal fails at ReduceConfig construction, and the "
      "registry (not a frozen tuple) is the single source of truth")
def unregistered_reduce_strategy(ctx):
    # reduce_strategies is deliberately numpy-only, so importing it keeps
    # the lint path jax-free; resolve lazily so a broken registry cannot
    # take down every other rule.
    from repro.core.reduce_strategies import registry_keys
    keys = registry_keys()
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        for kw in node.keywords:
            if kw.arg != "strategy":
                continue
            if isinstance(kw.value, ast.Constant) and \
                    isinstance(kw.value.value, str) and \
                    kw.value.value not in keys:
                yield (kw.value.lineno, kw.value.col_offset,
                       f"strategy={kw.value.value!r} is not a registered "
                       f"reduce strategy — registry keys are "
                       f"{', '.join(keys)} (register(...) a new one or "
                       f"fix the literal)")


# keep the module importable standalone for the docs generator
__all__ = [n for n in dir() if not n.startswith("_")]
