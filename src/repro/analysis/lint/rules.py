"""The lint rule registry and the five core serving-graph rules.

A rule is a function ``fn(graph, contract) -> list[Finding]`` registered
under a name (mirroring the pipeline's ``@register_stage`` idiom) —
external code can add project-specific rules without touching the runner:

    @register_rule("my-rule")
    def my_rule(graph, contract):
        return [Finding("my-rule", "error", jit="decode", where="...",
                        message="...")]

``graph`` is an ``extract.LintGraph`` (duck-typed — the tests drive rules
with hand-built miniatures); ``contract`` is the parsed contract JSON for
the recipe, or ``None`` when none exists yet (structural checks still run;
contract-relative budgets are skipped).

The five core rules:

  * **dtype-ledger** — no float materialization of int8 weights/KV on the
    serve path: every ``convert`` from s8 at full-cache size must feed a
    contraction (the scale folds downstream), never an elementwise
    dequantize-multiply. Decode jits are strict; the chunked-prefill dequant
    (by design, for now) must be pinned as contract ``known_debt``. All s8
    converts are tallied into a per-jit ledger diffed against the contract.
  * **collective-budget** — per-jit (count, bytes) of every collective kind
    must match the contract exactly; any collective whose result is a whole
    cache-pool leaf is an error under TP unless pinned as ``known_debt``
    (the PR-5 pooled ``take``/``.at[].set`` prefill gather).
  * **donation-audit** — every cache-pool leaf must appear in the compiled
    module's ``input_output_alias`` map on every engine jit (the pool
    updates in place; a dropped alias doubles cache HBM silently).
  * **recompilation-guard** — the dispatchable shape set (every prefill
    width / decode horizon the runtime can choose) must be CLOSED under the
    warmup set, and the warmup set must match the contract — a decode step
    may never introduce a new compiled shape.
  * **scale-coupling** — every int8 payload leaf's scale leaf shares its
    out-feature sharding axis (params) / its slot+head axes (KV cache), so
    a TP shard dequantizes locally without gathering foreign scales.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

from .hlo_model import HloModule, parse_array_type

# jaxpr primitives that consume an int8 operand *inside* the contraction —
# the convert is fused into the dot read, nothing f32-sized materializes
_FUSED_CONSUMERS = frozenset({"dot_general", "conv_general_dilated"})

SEVERITIES = ("error", "warn", "info")


@dataclasses.dataclass
class Finding:
    rule: str
    severity: str          # "error" | "warn" | "info"
    jit: str               # jit / kernel name ("" = recipe-level)
    where: str             # instruction name, leaf path, or shape signature
    message: str

    def __post_init__(self):
        if self.severity not in SEVERITIES:
            raise ValueError(f"severity must be one of {SEVERITIES}, "
                             f"got {self.severity!r}")

    def format(self) -> str:
        loc = f"{self.jit}:{self.where}" if self.where else self.jit
        return f"[{self.severity}] {self.rule} @ {loc}: {self.message}"


_RULES: dict[str, Callable] = {}


def register_rule(name: str):
    """Decorator: register ``fn(graph, contract) -> list[Finding]``."""

    def deco(fn):
        if name in _RULES:
            raise ValueError(f"lint rule {name!r} is already registered "
                             f"(by {_RULES[name].__module__})")
        _RULES[name] = fn
        return fn

    return deco


def list_rules() -> list[str]:
    return sorted(_RULES)


def run_rules(graph, contract: Optional[dict] = None,
              rules: Optional[list[str]] = None) -> list[Finding]:
    """Run ``rules`` (default: all registered) over one lint graph."""
    out: list[Finding] = []
    for name in rules or list_rules():
        try:
            fn = _RULES[name]
        except KeyError:
            raise ValueError(
                f"unknown lint rule {name!r}; registered: {list_rules()}"
            ) from None
        out.extend(fn(graph, contract))
    return out


# =========================================================== jaxpr analysis
@dataclasses.dataclass
class ConvertRecord:
    """One s8→float ``convert_element_type`` found in a traced jaxpr."""

    shape: tuple
    dtype: str
    elems: int
    consumers: tuple        # primitive names consuming the converted value
    in_pallas: bool         # inside a pallas_call body (VMEM tile — exempt)

    @property
    def fused(self) -> bool:
        return bool(self.consumers) and set(self.consumers) <= _FUSED_CONSUMERS


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for sub in vals:
            inner = getattr(sub, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner            # ClosedJaxpr
            elif hasattr(sub, "eqns"):
                yield sub              # raw Jaxpr


def _consumers_of(var, jaxpr, depth: int = 0) -> list[str]:
    """Primitive names that read ``var``, following 1:1 call-like primitives
    (pjit / scan map eqn.invars onto the body's invars index-wise) one level
    so a convert feeding ``pjit(dot_general)`` classifies as fused."""
    names: list[str] = []
    for eqn in jaxpr.eqns:
        if not any(v is var for v in eqn.invars):
            continue
        subs = list(_sub_jaxprs(eqn))
        followed = False
        if depth < 2 and len(subs) == 1:
            body = subs[0]
            body = getattr(body, "jaxpr", body)
            if len(body.invars) == len(eqn.invars):
                for i, v in enumerate(eqn.invars):
                    if v is var:
                        names.extend(
                            _consumers_of(body.invars[i], body, depth + 1))
                followed = True
        if not followed:
            names.append(eqn.primitive.name)
    return names


def s8_convert_records(closed_jaxpr) -> list[ConvertRecord]:
    """All s8→float converts in a (closed) jaxpr, recursing through scan /
    pjit / while bodies. Converts inside ``pallas_call`` kernels are tagged
    ``in_pallas`` — a blocked in-VMEM dequant is the kernel working as
    designed, not a graph-level materialization."""
    records: list[ConvertRecord] = []

    def walk(jaxpr, in_pallas: bool):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "convert_element_type":
                iv, ov = eqn.invars[0], eqn.outvars[0]
                src = getattr(getattr(iv, "aval", None), "dtype", None)
                dst = getattr(getattr(ov, "aval", None), "dtype", None)
                if (src is not None and str(src) == "int8"
                        and dst is not None and "float" in str(dst)
                        or str(dst) in ("bfloat16", "float16")
                        and str(src) == "int8"):
                    shape = tuple(ov.aval.shape)
                    elems = 1
                    for d in shape:
                        elems *= int(d)
                    records.append(ConvertRecord(
                        shape=shape, dtype=str(dst), elems=elems,
                        consumers=tuple(sorted(set(_consumers_of(ov, jaxpr)))),
                        in_pallas=in_pallas,
                    ))
            sub_pallas = in_pallas or eqn.primitive.name == "pallas_call"
            for sub in _sub_jaxprs(eqn):
                walk(getattr(sub, "jaxpr", sub), sub_pallas)

    walk(closed_jaxpr.jaxpr, False)
    return records


def convert_ledger(closed_jaxpr) -> dict:
    """Per-jit dtype ledger: totals + the materialized (non-fused) converts."""
    recs = s8_convert_records(closed_jaxpr)
    from .hlo_model import DTYPE_BYTES

    def nbytes(r):
        width = {"float32": 4, "bfloat16": 2, "float16": 2, "float64": 8}
        return r.elems * width.get(r.dtype, 4)

    return {
        "count": len(recs),
        "bytes": sum(nbytes(r) for r in recs),
        "materialized": [
            {"shape": list(r.shape), "dtype": r.dtype, "elems": r.elems,
             "consumers": list(r.consumers)}
            for r in recs if not r.fused and not r.in_pallas
        ],
    }


# ============================================================ HLO analysis
def collective_table(module: HloModule) -> dict[str, list]:
    """{base op: [count, total result bytes]} over every computation."""
    table: dict[str, list] = {}
    for instr in module.collectives():
        row = table.setdefault(instr.base_opcode, [0, 0])
        row[0] += 1
        row[1] += instr.result_bytes()
    return table


def pool_collective_hits(module: HloModule, artifact) -> list[dict]:
    """Collectives whose result is a whole cache-pool leaf (global or
    per-device shape, rank >= 2) — the pooled-gather pattern GSPMD inserts
    for ``take``/``.at[].set`` on a sharded pool.

    The paged pool's ``page_table`` is carved out of the matching: it is
    replicated, read-only inside every dispatch, and tiny (4 B per table
    entry), and its ``[num_slots, pages_per_slot]`` shape collides with
    TP reduction lattices like argmax's ``[B, model_shards]`` partials —
    matching it would flag every sharded argmax as whole-pool movement.
    Payload and kpos/pos leaves (the bytes that matter) stay matched."""
    targets = {
        (dt, dims)
        for dt, dims in (artifact.cache_leaves_global
                         + artifact.cache_leaves_local)
        if len(dims) >= 2
    } - set(artifact.page_table_shapes)
    hits = []
    for instr in module.collectives():
        for dt, dims in instr.result_shapes():
            if (dt, tuple(dims)) in targets:
                hits.append({
                    "op": instr.base_opcode, "instr": instr.name,
                    "type": f"{dt}[{','.join(map(str, dims))}]",
                    "bytes": instr.result_bytes(),
                })
                break
    return hits


def donation_info(module: HloModule, artifact) -> dict:
    """Compare the module's ``input_output_alias`` against the expected
    per-device cache-pool leaves."""
    expected = sorted(
        f"{dt}[{','.join(map(str, dims))}]"
        for dt, dims in artifact.cache_leaves_local
    )
    n_aliased = len(module.alias)
    aliased = []
    for t in module.aliased_param_types():
        try:
            dt, dims = parse_array_type(t)
            aliased.append(f"{dt}[{','.join(map(str, dims))}]")
        except ValueError:
            pass
    info = {"expected_leaves": len(expected), "aliased": n_aliased, "ok": True,
            "missing": []}
    if n_aliased < len(expected):
        info["ok"] = False
    if aliased:  # entry layout available: match leaf-for-leaf by (dtype, dims)
        remaining = sorted(aliased)
        missing = []
        for leaf in expected:
            if leaf in remaining:
                remaining.remove(leaf)
            else:
                missing.append(leaf)
        if missing:
            info["ok"] = False
            info["missing"] = missing
    return info


# ============================================================== known debt
def _debt_entries(contract: Optional[dict], rule: str, jit: str) -> list[dict]:
    if not contract:
        return []
    return [d for d in contract.get("known_debt", [])
            if d.get("rule") == rule and d.get("jit") == jit]


def _debt_covers(entries: list[dict], key: str, value) -> bool:
    return any(d.get(key) == value for d in entries)


# ============================================================== core rules
def is_cache_dequant(record: ConvertRecord, artifact) -> bool:
    """A materialized s8→float convert whose trailing dims are a whole
    cache-ring footprint ([..., S, Hkv·hd] int8) — the "full [B,S,H,hd]
    dequant" the paper-level invariant forbids. Weight dequants ([K,N],
    the w8a16 XLA-fallback scale-fold) never match: they are pinned by the
    ledger totals instead of erroring per instance."""
    dims = tuple(getattr(artifact, "cache_payload_dims", ()) or ())
    return (bool(dims) and len(record.shape) >= len(dims)
            and tuple(record.shape[-len(dims):]) == dims)


@register_rule("dtype-ledger")
def rule_dtype_ledger(graph, contract) -> list[Finding]:
    out: list[Finding] = []
    for name, art in graph.jits.items():
        if art.jaxpr is None:
            continue
        recs = s8_convert_records(art.jaxpr)
        for r in recs:
            if r.fused or r.in_pallas or not is_cache_dequant(r, art):
                continue
            shape = "x".join(map(str, r.shape))
            if art.kind == "decode":
                out.append(Finding(
                    "dtype-ledger", "error", name, shape,
                    f"s8 -> {r.dtype} convert materializes a full "
                    f"[{shape}] dequant (consumers: "
                    f"{', '.join(r.consumers) or 'none'}) on the decode "
                    f"path — int8 KV/weights must only be converted inside "
                    f"a contraction (scale-fold) or a Pallas tile",
                ))
            else:
                debt = _debt_entries(contract, "dtype-ledger", name)
                if _debt_covers(debt, "shape", list(r.shape)):
                    out.append(Finding(
                        "dtype-ledger", "info", name, shape,
                        "full-cache dequant pinned as known_debt "
                        "(chunked-prefill batched attention)",
                    ))
                else:
                    out.append(Finding(
                        "dtype-ledger", "error", name, shape,
                        f"s8 -> {r.dtype} convert materializes a full "
                        f"[{shape}] dequant not pinned in the contract's "
                        f"known_debt — run --update only if this "
                        f"materialization is intentional",
                    ))
        if contract:
            want = contract.get("jits", {}).get(name, {}).get("s8_converts")
            if want is not None:
                led = convert_ledger(art.jaxpr)
                for k in ("count", "bytes"):
                    if led[k] != want.get(k):
                        out.append(Finding(
                            "dtype-ledger", "error", name, k,
                            f"s8-convert ledger drift: {k} = {led[k]} but "
                            f"contract pins {want.get(k)} — the int8 path "
                            f"changed shape; rerun with --update if "
                            f"intentional",
                        ))
    return out


@register_rule("collective-budget")
def rule_collective_budget(graph, contract) -> list[Finding]:
    out: list[Finding] = []
    tp = bool(graph.mesh_shape) and graph.mesh_shape[-1] > 1
    for name, art in graph.jits.items():
        if art.module is None:
            continue
        table = collective_table(art.module)
        # pool-touching collectives: error under TP unless pinned as debt
        debt = _debt_entries(contract, "collective-budget", name)
        for hit in pool_collective_hits(art.module, art):
            if tp and not _debt_covers(debt, "type", hit["type"]):
                out.append(Finding(
                    "collective-budget", "error", name, hit["instr"],
                    f"{hit['op']} materializes a whole cache-pool leaf "
                    f"{hit['type']} ({hit['bytes']} B/device) — the pool "
                    f"must stay shard-resident under TP; pin as known_debt "
                    f"only with a ROADMAP item to remove it",
                ))
            elif tp:
                out.append(Finding(
                    "collective-budget", "info", name, hit["instr"],
                    f"pool-leaf {hit['op']} {hit['type']} covered by "
                    f"known_debt (pooled take/.at[].set gather)",
                ))
        if contract:
            want = contract.get("jits", {}).get(name, {}).get("collectives")
            if want is not None:
                for op in sorted(set(table) | set(want)):
                    got_c, got_b = table.get(op, [0, 0])
                    want_c, want_b = want.get(op, [0, 0])
                    if (got_c, got_b) != (want_c, want_b):
                        direction = ("new collective traffic"
                                     if got_b > want_b or got_c > want_c
                                     else "less traffic than pinned (a win "
                                          "— record it)")
                        out.append(Finding(
                            "collective-budget", "error", name, op,
                            f"{op}: {got_c} ops / {got_b} B vs contract "
                            f"{want_c} ops / {want_b} B — {direction}; "
                            f"run --update to re-pin",
                        ))
    return out


@register_rule("donation-audit")
def rule_donation_audit(graph, contract) -> list[Finding]:
    out: list[Finding] = []
    for name, art in graph.jits.items():
        if art.module is None or not art.cache_leaves_local:
            continue
        info = donation_info(art.module, art)
        if info["ok"]:
            continue
        missing = (", ".join(info["missing"]) if info["missing"]
                   else f"{info['expected_leaves'] - info['aliased']} leaves")
        out.append(Finding(
            "donation-audit", "error", name, "input_output_alias",
            f"cache-pool donation dropped: {info['aliased']} aliased "
            f"entry params but {info['expected_leaves']} pool leaves "
            f"(missing: {missing}) — without input_output_alias the pool "
            f"is copied every step (2x cache HBM + a memcpy per dispatch)",
        ))
    return out


@register_rule("recompilation-guard")
def rule_recompilation_guard(graph, contract) -> list[Finding]:
    out: list[Finding] = []
    extra = set(graph.dispatch_shapes) - set(graph.warmup_shapes)
    for jit, dim in sorted(extra):
        out.append(Finding(
            "recompilation-guard", "error", jit, str(dim),
            f"dispatchable shape ({jit}, {dim}) is not covered by "
            f"engine.warmup() — a live decode step would hit an XLA "
            f"compile mid-traffic; extend warmup_shapes() or quantize the "
            f"dispatch choice back onto the warmed set",
        ))
    if contract:
        want = {tuple(s) for s in contract.get("warmup_shapes", [])}
        got = {tuple(s) for s in graph.warmup_shapes}
        for jit, dim in sorted(got - want):
            out.append(Finding(
                "recompilation-guard", "error", str(jit), str(dim),
                f"new post-warmup shape ({jit}, {dim}) not in the "
                f"contract — the compiled-shape set grew; --update to "
                f"accept the new compile",
            ))
        for jit, dim in sorted(want - got):
            out.append(Finding(
                "recompilation-guard", "error", str(jit), str(dim),
                f"contract shape ({jit}, {dim}) is no longer compiled at "
                f"warmup — the warmed set shrank; --update to re-pin",
            ))
    return out


def _axis_entry(spec, dim: int):
    """Normalized axis assignment of ``spec`` at ``dim`` (None if the spec
    is shorter than the rank — trailing dims replicate)."""
    if spec is None:
        return None
    entries = tuple(spec)
    return entries[dim] if dim < len(entries) else None


@register_rule("scale-coupling")
def rule_scale_coupling(graph, contract) -> list[Finding]:
    out: list[Finding] = []
    leaves = graph.param_leaves or {}
    for q_path, s_path in graph.scale_pairs or []:
        q = leaves.get(q_path)
        s = leaves.get(s_path)
        if q is None:
            continue
        if s is None:
            out.append(Finding(
                "scale-coupling", "error", "params", q_path,
                f"int8 payload {q_path} has no scale leaf at {s_path} — "
                f"a QTensor without its scale cannot dequantize",
            ))
            continue
        q_axis = _axis_entry(q.get("spec"), len(q["shape"]) - 1)
        s_axis = _axis_entry(s.get("spec"), len(s["shape"]) - 1)
        per_tensor = not s["shape"] or s["shape"][-1] == 1
        if per_tensor:
            if s_axis is not None:
                out.append(Finding(
                    "scale-coupling", "error", "params", s_path,
                    f"per-tensor scale {s_path} is sharded on {s_axis!r} — "
                    f"a size-1 scale must replicate",
                ))
            continue
        if q_axis != s_axis:
            out.append(Finding(
                "scale-coupling", "error", "params", s_path,
                f"scale out-feature axis {s_axis!r} != payload out-feature "
                f"axis {q_axis!r} for {q_path} — a TP shard would gather "
                f"foreign scales to dequantize its own columns",
            ))
    # KV cache: scale / v_err leaves follow their payload's slot + head axes
    cache = graph.cache_spec_leaves or {}
    for pay_name, follow_name in (("k", "k_scale"), ("v", "v_scale"),
                                  ("v", "v_err")):
        pay = cache.get(f"/{pay_name}")
        fol = cache.get(f"/{follow_name}")
        if pay is None or fol is None:
            continue
        for dim, what in ((1, "slot"), (3, "head")):
            pa = _axis_entry(pay.get("spec"), dim)
            fa = _axis_entry(fol.get("spec"), dim)
            if (dim < len(fol["shape"]) and fol["shape"][dim] > 1
                    and pa != fa):
                out.append(Finding(
                    "scale-coupling", "error", "cache", f"/{follow_name}",
                    f"cache {follow_name} {what} axis {fa!r} != payload "
                    f"{pay_name} {what} axis {pa!r} — scales must live on "
                    f"their payload's shard",
                ))
    return out
