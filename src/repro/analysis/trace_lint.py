"""Jaxpr linter for the serving hot path.

The second static pass (docs/analysis.md#trace-lint): where
``kernel_contracts`` verifies the *dataflow mapping* of each kernel, this
module verifies the *trace* the jitted serving closures actually compile —
``ServingEngine.prefill`` and ``ServingEngine.decode`` are the two programs
that run per request, and a single host sync or silent fp64 upcast in
either one is a fleet-wide regression no parity test notices.

Rules (each is a ``LintFinding.rule``):

  host-callback        a host round-trip primitive (``pure_callback``,
                       ``io_callback``, ``debug_callback``/``debug_print``,
                       infeed/outfeed) inside the jitted trace — every
                       decode step would block on the host.
  fp64-promotion       an equation *produces* float64 from non-float64
                       inputs: a silent promotion (Python float + weak
                       types, ``np.float64`` constants) that doubles the
                       bandwidth of everything downstream.
  weak-type            a weakly-typed input to the traced closure: a
                       Python scalar reached ``jax.jit`` as an argument,
                       so every distinct value (or dtype context)
                       retraces and recompiles the whole program.
  int8-pool-no-scales  an int8 KV page pool flows into a ``pallas_call``
                       that receives no fp32 ``(P, Hkv)`` scale operands —
                       the kernel would consume raw quantized codes as if
                       they were values (docs/quant.md#kv-pages).

``lint_jaxpr`` walks any ClosedJaxpr recursively (jit bodies, scan/while
carries, cond branches); ``lint_engine`` traces a live ``ServingEngine``'s
prefill and decode closures with ``jax.make_jaxpr`` — abstract evaluation
only, nothing is executed and no device memory moves.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

import jax
import jax.numpy as jnp

__all__ = ["LintFinding", "lint_jaxpr", "lint_engine", "LINT_RULES"]

LINT_RULES = (
    "host-callback",
    "fp64-promotion",
    "weak-type",
    "int8-pool-no-scales",
)

# Primitive names that imply a host round-trip inside the trace.
_HOST_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "python_callback",
    "debug_callback", "debug_print",
    "infeed", "outfeed", "host_local_array_to_global_array",
})


@dataclasses.dataclass(frozen=True)
class LintFinding:
    """One hazard found in a traced program."""

    rule: str                  # one of LINT_RULES
    message: str
    path: str                  # e.g. "decode/jit:decode_step/scan"

    def __str__(self) -> str:
        return f"[{self.rule}] {self.path}: {self.message}"


def _aval(var) -> Optional[Any]:
    return getattr(var, "aval", None)


def _is_f64(var) -> bool:
    a = _aval(var)
    return a is not None and getattr(a, "dtype", None) == jnp.float64


def _sub_jaxprs(params: dict):
    """Yield (name, jaxpr) for every sub-jaxpr in an eqn's params —
    jit/scan/while bodies, cond branches — by duck-typing, so the walk
    survives jax version renames."""
    for key, val in params.items():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for i, v in enumerate(vals):
            inner = getattr(v, "jaxpr", None)       # ClosedJaxpr → Jaxpr
            if inner is not None and hasattr(inner, "eqns"):
                suffix = f"[{i}]" if len(vals) > 1 else ""
                yield f"{key}{suffix}", inner
            elif hasattr(v, "eqns"):                # bare Jaxpr
                suffix = f"[{i}]" if len(vals) > 1 else ""
                yield f"{key}{suffix}", v


def _walk(jaxpr, path: str, findings: List[LintFinding],
          check_weak_invars: bool) -> None:
    if check_weak_invars:
        for var in jaxpr.invars:
            a = _aval(var)
            if a is not None and getattr(a, "weak_type", False):
                findings.append(LintFinding(
                    "weak-type",
                    f"traced input {var} has a weak type "
                    f"({getattr(a, 'dtype', '?')}): a Python scalar reached "
                    f"the jitted closure as an argument — every new value "
                    f"context retraces; pass a committed jnp array instead",
                    path))
    for eqn in jaxpr.eqns:
        pname = eqn.primitive.name
        if pname in _HOST_PRIMITIVES:
            findings.append(LintFinding(
                "host-callback",
                f"primitive {pname!r} performs a host round-trip inside "
                f"the jitted trace; the accelerator stalls on the host "
                f"every step — hoist it out of the hot path",
                path))
        if (any(_is_f64(o) for o in eqn.outvars)
                and not any(_is_f64(i) for i in eqn.invars)):
            out_shapes = [getattr(_aval(o), "shape", ()) for o in eqn.outvars]
            findings.append(LintFinding(
                "fp64-promotion",
                f"primitive {pname!r} produces float64 {out_shapes} from "
                f"non-float64 inputs: a silent promotion (Python float / "
                f"np.float64 constant?) doubling downstream bandwidth — "
                f"cast explicitly or enable jax_default_dtype_bits=32",
                path))
        if pname == "pallas_call":
            _check_pallas_scales(eqn, path, findings)
        for sub_name, sub in _sub_jaxprs(eqn.params):
            sub_path = f"{path}/{pname}:{sub_name}"
            _walk(sub, sub_path, findings, check_weak_invars=False)


def _check_pallas_scales(eqn, path: str, findings: List[LintFinding]) -> None:
    """int8 KV pools (int8 operands of rank >= 3: the paged kernel sees
    (P, page_size, Hkv*D)) must be accompanied by fp32 (P, 1, Hkv) scale
    rows in the same call — the paged kernel's in-register dequant
    contract."""
    pools, scales = [], []
    for var in eqn.invars:
        a = _aval(var)
        if a is None:
            continue
        dtype = getattr(a, "dtype", None)
        shape = tuple(getattr(a, "shape", ()))
        if dtype == jnp.int8 and len(shape) >= 3:
            pools.append(shape)
        elif dtype == jnp.float32 and len(shape) == 3 and shape[1] == 1:
            scales.append(shape)
    n_scales = sum(1 for s in scales if any(s[0] == p[0] for p in pools))
    if pools and n_scales < len(pools):
        findings.append(LintFinding(
            "int8-pool-no-scales",
            f"pallas_call consumes {len(pools)} int8 page pool(s) "
            f"{pools} but only {n_scales} fp32 (P, 1, Hkv) scale "
            f"operand(s): the kernel would treat quantized codes as "
            f"values — pass kv_scales=(k_scales, v_scales) of shape "
            f"(P, Hkv)",
            path))


def lint_jaxpr(closed_jaxpr, *, path: str = "jaxpr",
               check_weak_invars: bool = True) -> List[LintFinding]:
    """Lint one ClosedJaxpr (as returned by ``jax.make_jaxpr(fn)(*args)``).

    Returns every finding; an empty list is the serving hot path's proof
    obligation (tests/test_analysis.py locks it in).
    """
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    findings: List[LintFinding] = []
    _walk(jaxpr, path, findings, check_weak_invars=check_weak_invars)
    return findings


def lint_engine(engine, *, prompt_len: int = 8,
                ) -> List[LintFinding]:
    """Trace a live ``ServingEngine``'s prefill and decode closures and
    lint both jaxprs.

    Uses the engine's real params/caches/block tables so the traced
    programs are exactly the ones ``generate()``/``step()`` dispatch —
    but via ``jax.make_jaxpr``, so this is abstract evaluation: nothing
    runs, no cache byte is touched.
    """
    B = engine.sc.batch_slots
    S = min(prompt_len, engine.sc.max_len)
    tokens = jnp.zeros((B, S), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))
    batch = {"tokens": tokens, "positions": positions,
             "last_cols": jnp.full((B,), S - 1, jnp.int32)}
    bt = None
    if engine.paged:
        bt = jnp.asarray(engine.block_tables, dtype=jnp.int32)
        batch["block_tables"] = bt

    findings: List[LintFinding] = []
    pf = jax.make_jaxpr(engine.prefill)(engine.params, batch, engine.caches)
    findings += lint_jaxpr(pf, path="prefill")
    tok1 = jnp.zeros((B, 1), jnp.int32)
    pos1 = jnp.full((B, 1), S, jnp.int32)
    dc = jax.make_jaxpr(engine.decode)(engine.params, tok1, pos1,
                                       engine.caches, bt)
    findings += lint_jaxpr(dc, path="decode")
    return findings
