"""Jaxpr contract checker — shard-safety/time/callback invariants, statically.

The engine traces a fn to a closed jaxpr (``jax.make_jaxpr`` — no execution,
no compilation) and propagates abstract *provenance tags* through the eqn
graph:

  * a **dimension tag** ``env`` marks axes that index environments (seeded
    on dim 0 of ``(E, ...)`` inputs, following them through broadcasts,
    transposes, reshapes, slices, scans, ...);
  * a **value tag** ``abs-time`` marks absolute-time values (the int32 tick
    counter, float64 absolute seconds).  Subtracting two absolute times
    yields a relative duration, which clears the tag — so the documented
    "rebase to window-relative, then narrow" pattern passes while a direct
    ``.astype(float32)`` of absolute time is flagged.

Rules checked per eqn (see :mod:`repro.analysis.contracts` for the catalog):
``env-contraction`` / ``env-gemm-rows`` (dot_general/conv touching an
env-tagged dim), ``env-reduce`` (reduce/cumsum/sort/argmax/top_k along an
env-tagged axis), ``collective``, ``time-cast`` (convert_element_type /
reduce_precision narrowing an abs-time value below float64 mantissa), and
``callback-in-scan`` (host callbacks at loop depth >= 1 — the checked entry
points are all scan-body-bound, so they start at depth 1 by default).

Propagation is conservative: an unknown primitive spreads every input tag
to every output dim, which can only create false positives, never false
negatives.  Higher-order primitives (pjit, scan, while, cond, shard_map,
custom_jvp/vjp, remat) are walked recursively; scan/while carries run to a
tag fixed point.
"""
from __future__ import annotations

import logging
import warnings
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.analysis.contracts import (
    ContractViolation, Violation, TAG_ENV, TAG_MASK, TAG_TIME,
)

logger = logging.getLogger(__name__)

EMPTY = frozenset()


class Rules(NamedTuple):
    """Which rule families a check enforces.

    ``env`` is the shard-invariance family — enforced for the ``*_sharded``
    modes (the fused non-sharded engine may legally run a non-row-wise
    model, e.g. examples/serve_edge.py's LM policy).  ``carry`` enables the
    ``carry-env-mix`` row-movement checks (rev/roll/narrowing-slice/gather
    along an env-tagged axis) — on for policy certification
    (:mod:`repro.analysis.certify`), where a recurrent carry rides the
    fused scan and a row permutation would silently cross shard boundaries;
    off by default so pre-certification callers keep their exact rule set.
    ``mask`` enables the ``env-mask-gate`` family (elastic slot pools):
    mask-derived values must combine multiplicatively/by-select and never
    drive compaction or index math — auto-enabled by
    :func:`check_decide_fns` when the decide state carries an ``active``
    mask leaf.  The other families hold for every checked fn.
    """
    env: bool = True
    collectives: bool = True
    callbacks: bool = True
    time: bool = True
    carry: bool = False
    mask: bool = False


class Prov(NamedTuple):
    """Provenance of one jaxpr value: per-dimension tag sets + value tags."""
    dims: tuple            # tuple[frozenset[str], ...], len == ndim
    val: frozenset = EMPTY


def _empty(ndim: int) -> Prov:
    return Prov((EMPTY,) * ndim)


def _fit(p: Prov, ndim: int) -> Prov:
    """Defensive rank fix-up: never lose a tag to a rank mismatch."""
    if len(p.dims) == ndim:
        return p
    spread = frozenset().union(*p.dims) if p.dims else EMPTY
    return Prov((spread,) * ndim, p.val)


def _align_union(ins: Sequence[Prov], out_ndim: int) -> Prov:
    """Right-aligned per-dim union (elementwise ops with rank broadcasting)."""
    dims = [EMPTY] * out_ndim
    val = EMPTY
    for p in ins:
        off = out_ndim - len(p.dims)
        for j, t in enumerate(p.dims):
            if 0 <= j + off < out_ndim:
                dims[j + off] = dims[j + off] | t
        val = val | p.val
    return Prov(tuple(dims), val)


# --- primitive classification ------------------------------------------------

_ELEMENTWISE = frozenset("""
abs add and atan2 cbrt ceil clamp copy cos cosh cumlogsumexp device_put div
eq erf erfc erf_inv exp exp2 expm1 floor ge gt imag integer_pow is_finite le
log log1p logistic lt max min mul ne neg nextafter not or population_count
pow real regularized_incomplete_beta rem round rsqrt select_n shift_left
shift_right_arithmetic shift_right_logical sign sin sinh sqrt square
stop_gradient sub tan tanh xor acos asin atan acosh asinh atanh digamma
lgamma igamma igammac bessel_i0e bessel_i1e clz
""".split())

_REDUCES = frozenset(
    ["reduce_sum", "reduce_prod", "reduce_max", "reduce_min",
     "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
     "reduce_precision_reduce"])  # last: defensive name, never matches

_CUMULATIVE = frozenset(["cumsum", "cumprod", "cummax", "cummin",
                         "cumlogsumexp"])

_COLLECTIVES = frozenset(
    ["psum", "pmax", "pmin", "pmean", "ppermute", "pshuffle", "all_gather",
     "all_to_all", "reduce_scatter", "psum_scatter", "axis_index",
     "pbroadcast", "pgather", "pdot"])

_CALLBACKS = frozenset(
    ["pure_callback", "io_callback", "debug_callback", "debug_print",
     "callback", "outside_call", "host_callback_call", "python_callback"])

# higher-order prims handled structurally
_SUBJAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _src_of(eqn) -> str:
    try:
        return compat.source_summary(eqn)
    except Exception:
        return "<unknown>"


def _is_jaxpr_like(obj) -> bool:
    return hasattr(obj, "eqns") or (hasattr(obj, "jaxpr")
                                    and hasattr(obj, "consts"))


def _open(j):
    """ClosedJaxpr -> Jaxpr (constvars get empty provs in _run)."""
    return j.jaxpr if hasattr(j, "consts") else j


class _Ctx:
    def __init__(self, rules: Rules, label: str):
        self.rules = rules
        self.label = label
        self.violations = []
        self._seen = set()

    def add(self, rule, message, primitive, source):
        key = (rule, primitive, source)
        if key in self._seen:    # scan fixed-point re-runs revisit eqns
            return
        self._seen.add(key)
        self.violations.append(Violation(rule=rule, message=message,
                                         primitive=primitive, source=source,
                                         label=self.label))


# --- per-eqn rule checks ------------------------------------------------------

def _check_eqn(eqn, name, ins, ctx: _Ctx, loop_depth: int):
    rules = ctx.rules
    if rules.collectives and name in _COLLECTIVES:
        ctx.add("collective",
                f"collective '{name}' in a shard_map-bound fn: the sharded "
                "engines are collective-free by contract (cross-env math "
                "belongs on the host)", name, _src_of(eqn))
    if rules.callbacks and name in _CALLBACKS and loop_depth >= 1:
        ctx.add("callback-in-scan",
                f"host callback '{name}' inside a scan/while body: a hidden "
                "host sync per scan step defeats the one-dispatch-per-batch "
                "engine (log after the batch instead)", name, _src_of(eqn))
    if rules.time and name == "convert_element_type":
        new = np.dtype(eqn.params["new_dtype"])
        p = ins[0]
        if TAG_TIME in p.val and np.issubdtype(new, np.floating):
            nmant = np.finfo(new).nmant
            old = np.dtype(eqn.invars[0].aval.dtype)
            already_narrow = (np.issubdtype(old, np.floating)
                              and np.finfo(old).nmant <= nmant)
            if nmant < 52 and not already_narrow:
                ctx.add("time-cast",
                        f"absolute-time value cast {old.name} -> {new.name}:"
                        " float32 absolute seconds/ticks quantize past "
                        "t~2^24 (consecutive window ends collapse to the "
                        "same value). Keep absolute time in float64/int32 "
                        "and rebase to window-relative (subtract a time) "
                        "before narrowing", name, _src_of(eqn))
    if rules.time and name == "reduce_precision":
        if TAG_TIME in ins[0].val and eqn.params.get("mantissa_bits", 53) < 52:
            ctx.add("time-cast",
                    "reduce_precision narrows an absolute-time value below "
                    "float64 mantissa; rebase to window-relative first",
                    name, _src_of(eqn))
    if rules.mask:
        _check_mask_gate(eqn, name, ins, ctx)
    if not rules.env:
        return
    if name == "dot_general":
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs, rhs = ins[0], ins[1]
        contracted = (any(TAG_ENV in lhs.dims[d] for d in lc)
                      or any(TAG_ENV in rhs.dims[d] for d in rc))
        anywhere = (any(TAG_ENV in t for t in lhs.dims)
                    or any(TAG_ENV in t for t in rhs.dims))
        if contracted:
            ctx.add("env-contraction",
                    "dot_general contracts over the env axis: the result "
                    "mixes rows across environments and diverges between "
                    "the sharded and unsharded engines", name, _src_of(eqn))
        elif anywhere:
            ctx.add("env-gemm-rows",
                    "env rows feed a dot_general: XLA:CPU lowers (rows, F) "
                    "gemms through row-count-dependent kernels, so the bits "
                    "depend on rows-per-device (1-ulp shard drift). Phrase "
                    "per-env dots as multiply+reduce over features (see "
                    "runtime.predictor.linear_policy)", name, _src_of(eqn))
    elif name == "conv_general_dilated":
        if any(TAG_ENV in t for p in ins[:2] for t in p.dims):
            ctx.add("env-gemm-rows",
                    "env rows feed a convolution: lowering is "
                    "row-count-dependent; keep the env axis out of conv "
                    "operands (vmap-free per-env math)", name, _src_of(eqn))
    elif name in _REDUCES and "axes" in eqn.params:
        bad = [a for a in eqn.params["axes"] if TAG_ENV in ins[0].dims[a]]
        if bad:
            ctx.add("env-reduce",
                    f"'{name}' reduces along the env axis (axis {bad[0]}): "
                    "per-env decision math must not mix rows across "
                    "environments (a cross-env mean/sum diverges under the "
                    "env-sharded engine)", name, _src_of(eqn))
    elif name in _CUMULATIVE:
        ax = eqn.params.get("axis", 0)
        if TAG_ENV in ins[0].dims[ax]:
            ctx.add("env-reduce",
                    f"'{name}' scans along the env axis: row i depends on "
                    "rows < i, which is cross-env math", name, _src_of(eqn))
    elif name == "sort":
        d = eqn.params.get("dimension", len(ins[0].dims) - 1)
        if any(TAG_ENV in p.dims[d] for p in ins if len(p.dims) > d):
            ctx.add("env-reduce",
                    "'sort' permutes along the env axis: rows move across "
                    "environments", name, _src_of(eqn))
    elif name == "top_k":
        if ins[0].dims and TAG_ENV in ins[0].dims[-1]:
            ctx.add("env-reduce",
                    "'top_k' selects along the env axis: rows mix across "
                    "environments", name, _src_of(eqn))
    if rules.carry:
        _check_row_moves(eqn, name, ins, ctx)


def _check_mask_gate(eqn, name, ins, ctx: _Ctx):
    """``env-mask-gate`` eqn checks: a mask-derived value (the elastic
    ``active``/``prev_ok`` carry leaves and anything computed from them)
    may GATE values — multiply/AND/where — but must never DRIVE structure:
    row-compaction offsets (a cumsum of the mask along the env axis),
    ordering (sort/top_k), or index math (gather/scatter/dynamic_slice
    start operands).  Structural use changes row placement with membership
    — exactly what the no-retrace, bit-exact-active-rows contract
    forbids."""
    def flag(detail):
        ctx.add("env-mask-gate",
                f"{detail} — the elastic active mask combines only "
                "multiplicatively or via select/where (row i's output "
                "depends on row i's mask bit alone); mask-derived "
                "compaction/ordering/index math moves rows with membership "
                "and breaks the no-retrace, bit-exact-active-rows contract",
                name, _src_of(eqn))

    if name in ("sort", "top_k"):
        if any(TAG_MASK in p.val for p in ins):
            flag(f"'{name}' orders by a mask-derived value")
    elif name in _CUMULATIVE:
        ax = eqn.params.get("axis", 0)
        if TAG_MASK in ins[0].val and ax < len(ins[0].dims) \
                and TAG_ENV in ins[0].dims[ax]:
            flag(f"'{name}' scans a mask-derived value along the env axis "
                 "(the row-compaction-offset pattern)")
    elif name in ("argmax", "argmin"):
        axes = eqn.params.get("axes", ())
        if TAG_MASK in ins[0].val and any(
                a < len(ins[0].dims) and TAG_ENV in ins[0].dims[a]
                for a in axes):
            flag(f"'{name}' picks a row position from a mask-derived value "
                 "along the env axis")
    elif name == "gather":
        if len(ins) > 1 and TAG_MASK in ins[1].val:
            flag("'gather' indexes with a mask-derived value")
    elif name.startswith("scatter"):
        if len(ins) > 1 and TAG_MASK in ins[1].val:
            flag("'scatter' indexes with a mask-derived value (masking "
                 "belongs in the UPDATE values, not the indices)")
    elif name == "dynamic_slice":
        if any(TAG_MASK in p.val for p in ins[1:]):
            flag("'dynamic_slice' start indices derive from the mask")
    elif name == "dynamic_update_slice":
        if any(TAG_MASK in p.val for p in ins[2:]):
            flag("'dynamic_update_slice' start indices derive from the "
                 "mask")


def _check_row_moves(eqn, name, ins, ctx: _Ctx):
    """``carry-env-mix`` eqn checks: primitives that MOVE rows along an
    env-tagged axis (reverse/roll/subset-slice/gather).  Elementwise math
    keeps row i's data in row i, so the base rules let these pass; for a
    recurrent carry they re-route state across environments — and across
    shard boundaries, without a collective, under the env mesh."""
    def flag(detail):
        ctx.add("carry-env-mix",
                f"{detail} — a recurrent carry (and everything feeding it) "
                "must keep env row i's state in row i; under the "
                "env-sharded fused scan this crosses shard boundaries "
                "without a collective", name, _src_of(eqn))

    if name == "rev":
        bad = [d for d in eqn.params["dimensions"]
               if d < len(ins[0].dims) and TAG_ENV in ins[0].dims[d]]
        if bad:
            flag(f"'rev' reverses the env axis (dim {bad[0]})")
    elif name == "concatenate":
        d = eqn.params["dimension"]
        if any(len(p.dims) > d and TAG_ENV in p.dims[d] for p in ins):
            flag(f"'concatenate' stacks along the env axis (dim {d}): "
                 "row order/count changes (the jnp.roll lowering)")
    elif name == "slice":
        starts = eqn.params["start_indices"]
        limits = eqn.params["limit_indices"]
        strides = eqn.params["strides"] or (1,) * len(starts)
        shape = tuple(eqn.invars[0].aval.shape)
        for d, t in enumerate(ins[0].dims):
            if TAG_ENV in t and (starts[d] != 0 or limits[d] != shape[d]
                                 or strides[d] != 1):
                flag(f"'slice' selects a subset of env rows (dim {d}: "
                     f"[{starts[d]}:{limits[d]}:{strides[d]}] of "
                     f"{shape[d]})")
                break
    elif name == "dynamic_slice":
        sizes = eqn.params["slice_sizes"]
        shape = tuple(eqn.invars[0].aval.shape)
        for d, t in enumerate(ins[0].dims):
            if TAG_ENV in t and sizes[d] != shape[d]:
                flag(f"'dynamic_slice' narrows the env axis (dim {d}: "
                     f"{sizes[d]} of {shape[d]} rows)")
                break
    elif name == "dynamic_update_slice":
        op_shape = tuple(eqn.invars[0].aval.shape)
        upd_shape = tuple(eqn.invars[1].aval.shape)
        for d, t in enumerate(ins[0].dims):
            if TAG_ENV in t and d < len(upd_shape) \
                    and upd_shape[d] != op_shape[d]:
                flag(f"'dynamic_update_slice' writes a subset of env rows "
                     f"(dim {d}: {upd_shape[d]} of {op_shape[d]})")
                break
    elif name == "gather":
        sizes = eqn.params.get("slice_sizes", ())
        shape = tuple(eqn.invars[0].aval.shape)
        for d, t in enumerate(ins[0].dims):
            if TAG_ENV in t and d < len(sizes) and sizes[d] != shape[d]:
                flag(f"'gather' indexes along the env axis (dim {d}: "
                     f"slice size {sizes[d]} of {shape[d]} rows)")
                break
    elif name == "pad":
        cfg = eqn.params["padding_config"]
        for d, t in enumerate(ins[0].dims):
            if TAG_ENV in t and d < len(cfg) and any(cfg[d]):
                flag(f"'pad' shifts row alignment on the env axis (dim "
                     f"{d}: padding {cfg[d]})")
                break


# --- propagation --------------------------------------------------------------

def _out_ndims(eqn):
    return [getattr(v.aval, "ndim", 0) for v in eqn.outvars]


def _reshape_prov(p: Prov, in_shape, out_shape) -> Prov:
    """Map dim tags through a reshape by matching size-group boundaries."""
    if 0 in in_shape or 0 in out_shape:
        return _fit(p, len(out_shape))
    out = [EMPTY] * len(out_shape)
    i = j = 0
    while i < len(in_shape) or j < len(out_shape):
        ip, jp, gi, gj = 1, 1, [], []
        if i < len(in_shape):
            ip *= in_shape[i]; gi.append(i); i += 1
        if j < len(out_shape):
            jp *= out_shape[j]; gj.append(j); j += 1
        while ip != jp:
            if ip < jp and i < len(in_shape):
                ip *= in_shape[i]; gi.append(i); i += 1
            elif jp < ip and j < len(out_shape):
                jp *= out_shape[j]; gj.append(j); j += 1
            else:
                return _fit(p, len(out_shape))   # unmatched (trailing 1s...)
        tags = frozenset().union(*(p.dims[d] for d in gi)) if gi else EMPTY
        for d in gj:
            out[d] = out[d] | tags
    return Prov(tuple(out), p.val)


def _prop_scanlike(body, ins, n_consts, n_carry, ctx, loop_depth,
                   xs_drop_leading=True):
    """scan-style propagation with a carry tag fixed point."""
    consts = list(ins[:n_consts])
    carry = list(ins[n_consts:n_consts + n_carry])
    xs = [Prov(p.dims[1:], p.val) if (xs_drop_leading and p.dims) else p
          for p in ins[n_consts + n_carry:]]
    outs = []
    for _ in range(8):
        outs = _run(_open(body), consts + carry + xs, ctx, loop_depth + 1)
        new_carry = []
        changed = False
        for old, new in zip(carry, outs[:n_carry]):
            new = _fit(new, len(old.dims))
            merged = Prov(tuple(a | b for a, b in zip(old.dims, new.dims)),
                          old.val | new.val)
            changed = changed or merged != old
            new_carry.append(merged)
        carry = new_carry
        if not changed:
            break
    ys = [Prov((EMPTY,) + p.dims, p.val) for p in outs[n_carry:]]
    return outs[:n_carry] + ys


_PALLAS_GRID_CAP = 4096  # max grid points to evaluate index maps over


def _eval_index_map(bm, point):
    """Evaluate one BlockSpec index map at a concrete grid point."""
    res = compat.eval_jaxpr(bm.index_map_jaxpr,
                            *(np.int32(i) for i in point))
    return tuple(int(np.asarray(r)) for r in res)


def _prop_pallas(eqn, ins, ctx, loop_depth):
    """Descend into a ``pallas_call``: map BlockSpec index maps onto the env
    tag instead of conservatively poisoning the outputs.

    Per grid instance, an env-tagged operand dim must be blocked size-1
    (each kernel instance sees exactly one env row), and every env-tagged
    input and output must agree on WHICH env block the instance touches —
    an input map reading env block ``g(i)`` while the output writes block
    ``i`` routes rows across environments (``pallas-env-block``).  The
    kernel jaxpr is then walked with the env dim dropped (a size-1 block
    carries no cross-env structure) so callback/time/collective rules see
    inside the kernel.  Any unexpected structure raises, which the caller
    turns into the conservative spread-all fallback.
    """
    params = eqn.params
    gm = compat.pallas_grid_mapping(eqn)
    kernel = _open(params["jaxpr"])
    grid = tuple(gm.grid)
    nouts = _out_ndims(eqn)
    if (getattr(gm, "num_dynamic_grid_bounds", 0)
            or not all(isinstance(g, (int, np.integer)) for g in grid)
            or int(np.prod(grid, dtype=np.int64) if grid else 1)
            > _PALLAS_GRID_CAP):
        raise NotImplementedError("dynamic or oversized pallas grid")
    n_in, n_out = gm.num_inputs, gm.num_outputs
    mappings = list(gm.block_mappings)
    assert len(mappings) == n_in + n_out, (len(mappings), n_in, n_out)
    # eqn.invars may lead with scalar-prefetch/index operands; the block
    # operands are the trailing n_in
    off = len(ins) - n_in
    assert off >= 0, (len(ins), n_in)
    points = list(np.ndindex(*grid)) if grid else [()]

    def block_size(bm, d):
        return compat.pallas_block_sizes(bm)[d]

    # the env-block index function each instance must agree on, from the
    # env-tagged inputs
    env_fn = None          # tuple of per-point env block indices
    env_extent = None
    for i in range(n_in):
        p = ins[off + i]
        bm = mappings[i]
        shape = tuple(eqn.invars[off + i].aval.shape)
        for d, t in enumerate(p.dims):
            if TAG_ENV not in t:
                continue
            if block_size(bm, d) != 1:
                if ctx.rules.env:
                    ctx.add("pallas-env-block",
                            f"input {i} blocks its env axis (dim {d}) with "
                            f"size {block_size(bm, d)}: each kernel "
                            "instance sees multiple env rows, so the "
                            "kernel body can mix them; block env dims "
                            "size-1", "pallas_call", _src_of(eqn))
                raise NotImplementedError("env dim not size-1 blocked")
            fn = tuple(_eval_index_map(bm, pt)[d] for pt in points)
            if env_fn is None:
                env_fn, env_extent = fn, shape[d]
            elif fn != env_fn:
                if ctx.rules.env:
                    ctx.add("pallas-env-block",
                            f"input {i}'s env-axis index map (dim {d}) "
                            "disagrees with another env-tagged operand's: "
                            "one kernel instance combines rows of "
                            "different environments", "pallas_call",
                            _src_of(eqn))
                raise NotImplementedError("env index maps disagree")

    if env_fn is None:
        # no env-tagged operands: nothing shard-shaped to track precisely
        raise NotImplementedError("no env-tagged pallas operands")

    # outputs: an output dim matching (extent, size-1 block, same index
    # function) inherits the env tag; an output with a candidate env dim
    # whose index function DIFFERS is cross-env routing
    out_provs = []
    in_val = frozenset().union(EMPTY, *(p.val for p in ins))
    for o in range(n_out):
        bm = mappings[n_in + o]
        shape = tuple(eqn.outvars[o].aval.shape)
        dims = [EMPTY] * nouts[o]
        matched = False
        mismatched = None
        for d in range(len(shape)):
            if shape[d] != env_extent or block_size(bm, d) != 1:
                continue
            fn = tuple(_eval_index_map(bm, pt)[d] for pt in points)
            if fn == env_fn:
                dims[d] = frozenset({TAG_ENV})
                matched = True
            else:
                mismatched = d
        if not matched and mismatched is not None:
            if ctx.rules.env:
                ctx.add("pallas-env-block",
                        f"output {o}'s index map routes env blocks "
                        f"differently from the inputs' (dim {mismatched}): "
                        "a kernel instance reading env block g writes a "
                        "different env block — rows cross environments",
                        "pallas_call", _src_of(eqn))
            dims = [frozenset({TAG_ENV})] * nouts[o]   # poison, it's wrong
        elif not matched:
            dims = [frozenset({TAG_ENV})] * nouts[o]   # conservative
        out_provs.append(Prov(tuple(dims), in_val))

    # walk the kernel body with env dims dropped (size-1 blocks): the
    # callback/time/collective rules apply inside the kernel too
    k_provs = []
    for j, v in enumerate(kernel.invars):
        knd = getattr(v.aval, "ndim", 0)
        i = j - (len(kernel.invars) - n_in - n_out - (
            getattr(gm, "num_scratch_operands", 0)))
        src = ins[off + i] if 0 <= i < n_in else _empty(knd)
        p = _fit(src, knd)
        k_provs.append(Prov(tuple(t - {TAG_ENV} for t in p.dims), p.val))
    _run(kernel, k_provs, ctx, loop_depth + 1)
    return out_provs


def _propagate(eqn, name, ins, ctx, loop_depth):
    params = eqn.params
    nouts = _out_ndims(eqn)

    if name in _ELEMENTWISE or name in _CUMULATIVE or name == "select_n" \
            or name == "clamp" or name == "reduce_precision":
        out = _align_union(ins, nouts[0])
        if name == "select_n" and len(ins) >= 2 \
                and TAG_MASK in ins[0].val:
            # the predicate only GATES a select: the output's VALUES come
            # from the branches, so the mask tag does not leak through a
            # where/select — the sanctioned mask combinator stays clean
            branch_val = frozenset().union(EMPTY,
                                           *(p.val for p in ins[1:]))
            if TAG_MASK not in branch_val:
                out = Prov(out.dims, out.val - {TAG_MASK})
        if name == "sub" and len(ins) == 2 \
                and TAG_TIME in ins[0].val and TAG_TIME in ins[1].val:
            # t_a - t_b is a relative duration: the abs-time tag clears,
            # so "rebase to window-relative, then narrow" passes
            out = Prov(out.dims, out.val - {TAG_TIME})
        if name == "rem" and len(ins) == 2 \
                and TAG_TIME in ins[0].val and TAG_TIME not in ins[1].val:
            # t mod period is phase, bounded by the (untagged) divisor
            out = Prov(out.dims, out.val - {TAG_TIME})
        return [out] * len(nouts)

    if name == "convert_element_type" or name == "copy" \
            or name == "device_put":
        return [_fit(ins[0], nouts[0])]

    if name == "optimization_barrier":
        # identity per operand (out i is in i, fusion-sealed) — the elastic
        # mask discipline barriers decision math before its gating selects
        return [_fit(p, n) for p, n in zip(ins, nouts)]

    if name == "broadcast_in_dim":
        bd = params["broadcast_dimensions"]
        dims = [EMPTY] * nouts[0]
        for src, dst in enumerate(bd):
            dims[dst] = ins[0].dims[src]
        return [Prov(tuple(dims), ins[0].val)]

    if name == "transpose":
        perm = params["permutation"]
        return [Prov(tuple(ins[0].dims[p] for p in perm), ins[0].val)]

    if name == "reshape":
        in_shape = tuple(eqn.invars[0].aval.shape)
        out_shape = tuple(eqn.outvars[0].aval.shape)
        return [_reshape_prov(ins[0], in_shape, out_shape)]

    if name == "squeeze":
        drop = set(params["dimensions"])
        dims = tuple(t for d, t in enumerate(ins[0].dims) if d not in drop)
        return [Prov(dims, ins[0].val)]

    if name == "expand_dims":
        add = set(params["dimensions"])
        dims, src = [], iter(ins[0].dims)
        for d in range(nouts[0]):
            dims.append(EMPTY if d in add else next(src, EMPTY))
        return [Prov(tuple(dims), ins[0].val)]

    if name in ("slice", "rev", "dynamic_slice"):
        return [_fit(ins[0], nouts[0])]

    if name == "split":
        return [_fit(ins[0], n) for n in nouts]

    if name == "concatenate":
        return [_align_union(ins, nouts[0])]

    if name == "pad":
        return [_fit(ins[0], nouts[0])]

    if name == "dynamic_update_slice":
        return [_align_union(ins[:2], nouts[0])]

    if name in _REDUCES:
        axes = set(params.get("axes", ()))
        dims = tuple(t for d, t in enumerate(ins[0].dims) if d not in axes)
        return [Prov(dims, ins[0].val)] * len(nouts)

    if name == "dot_general":
        (lc, rc), (lb, rb) = params["dimension_numbers"]
        lhs, rhs = ins[0], ins[1]
        lf = [d for d in range(len(lhs.dims)) if d not in lc and d not in lb]
        rf = [d for d in range(len(rhs.dims)) if d not in rc and d not in rb]
        dims = ([lhs.dims[a] | rhs.dims[b] for a, b in zip(lb, rb)]
                + [lhs.dims[d] for d in lf] + [rhs.dims[d] for d in rf])
        return [Prov(tuple(dims), lhs.val | rhs.val)]

    if name.startswith("scatter"):
        op, upd = ins[0], ins[2] if len(ins) > 2 else ins[0]
        if len(upd.dims) == len(op.dims):
            return [_align_union([op, upd], nouts[0])]
        spread = frozenset().union(EMPTY, *op.dims, *upd.dims)
        return [Prov(tuple(t | spread for t in op.dims), op.val | upd.val)]

    if name == "gather":
        spread = frozenset().union(EMPTY, *(t for p in ins for t in p.dims))
        val = frozenset().union(EMPTY, *(p.val for p in ins))
        return [Prov((spread,) * nouts[0], val)]

    if name in ("iota", "rng_bit_generator", "random_seed", "random_wrap",
                "random_bits", "random_unwrap"):
        return [_empty(n) for n in nouts]

    if name == "sort":
        return [_fit(p, n) for p, n in zip(ins, nouts)]

    if name == "top_k":
        return [_fit(ins[0], n) for n in nouts]

    if name == "pallas_call":
        return _prop_pallas(eqn, ins, ctx, loop_depth)

    if name == "scan":
        return _prop_scanlike(params["jaxpr"], ins, params["num_consts"],
                              params["num_carry"], ctx, loop_depth)

    if name == "while":
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        carry_in = ins[cn + bn:]
        # cond runs with (cond_consts + carry); walk it for rule checks
        _run(_open(params["cond_jaxpr"]), list(ins[:cn]) + list(carry_in),
             ctx, loop_depth + 1)
        body_ins = list(ins[cn:cn + bn]) + list(carry_in)
        outs = _prop_scanlike(params["body_jaxpr"], body_ins, bn,
                              len(carry_in), ctx, loop_depth,
                              xs_drop_leading=False)
        return outs[:len(carry_in)]

    if name == "cond":
        branch_outs = [_run(_open(br), ins[1:], ctx, loop_depth)
                       for br in params["branches"]]
        merged = []
        for i, n in enumerate(nouts):
            ps = [_fit(bo[i], n) for bo in branch_outs]
            merged.append(_align_union(ps, n))
        return merged

    # generic higher-order fallback (pjit, custom_jvp/vjp, remat,
    # shard_map, closed_call, ...): exactly one jaxpr-like param whose
    # invars line up 1:1 with the eqn's
    sub = None
    for k in _SUBJAXPR_KEYS:
        if k in params and _is_jaxpr_like(params[k]):
            sub = params[k]
            break
    if sub is not None:
        inner = _open(sub)
        n = len(inner.invars)
        sub_ins = list(ins[:n]) + [_empty(getattr(v.aval, "ndim", 0))
                                   for v in inner.invars[len(ins):]]
        sub_ins = [_fit(p, getattr(v.aval, "ndim", 0))
                   for p, v in zip(sub_ins, inner.invars)]
        outs = _run(inner, sub_ins, ctx, loop_depth)
        outs = outs[:len(nouts)]
        outs += [_empty(n) for n in nouts[len(outs):]]
        return [_fit(p, n) for p, n in zip(outs, nouts)]

    # unknown primitive: conservative — spread every tag over every out dim
    spread = frozenset().union(EMPTY, *(t for p in ins for t in p.dims))
    val = frozenset().union(EMPTY, *(p.val for p in ins))
    return [Prov((spread,) * n, val) for n in nouts]


def _run(jaxpr, in_provs, ctx: _Ctx, loop_depth: int):
    """Walk one (open) jaxpr; returns the outvar provs."""
    env = {}

    def read(a):
        if hasattr(a, "val"):          # Literal
            return _empty(np.ndim(a.val))
        return env.get(a, _empty(getattr(a.aval, "ndim", 0)))

    for v, p in zip(jaxpr.invars, in_provs):
        env[v] = _fit(p, getattr(v.aval, "ndim", 0))
    for v in jaxpr.constvars:
        env[v] = _empty(getattr(v.aval, "ndim", 0))

    for eqn in jaxpr.eqns:
        ins = [read(x) for x in eqn.invars]
        name = eqn.primitive.name
        _check_eqn(eqn, name, ins, ctx, loop_depth)
        try:
            outs = _propagate(eqn, name, ins, ctx, loop_depth)
        except Exception:   # propagation must never mask the real trace
            logger.debug("propagation fell back for '%s'", name,
                         exc_info=True)
            spread = frozenset().union(
                EMPTY, *(t for p in ins for t in p.dims))
            val = frozenset().union(EMPTY, *(p.val for p in ins))
            outs = [Prov((spread,) * n, val) for n in _out_ndims(eqn)]
        for v, p in zip(eqn.outvars, outs):
            env[v] = _fit(p, getattr(v.aval, "ndim", 0))
    return [read(x) for x in jaxpr.outvars]


# --- public API ----------------------------------------------------------------

def _parse_tag(tag: str, ndim: int) -> Prov:
    """Tag spec -> Prov.  '' | 'env:0' | 'time' | 'mask' | 'env:0,mask'."""
    dims = [EMPTY] * ndim
    val = EMPTY
    for part in filter(None, (tag or "").split(",")):
        if part == "mask":
            val = val | {TAG_MASK}
        elif part.startswith("env"):
            d = int(part.split(":")[1]) if ":" in part else 0
            if d < ndim:
                dims[d] = dims[d] | {TAG_ENV}
        elif part == "time":
            val = val | {TAG_TIME}
        else:
            raise ValueError(f"unknown provenance tag {part!r}")
    return Prov(tuple(dims), val)


def check_fn(fn: Callable, args, tags, *, rules: Rules = Rules(),
             label: str = "", scan_bound: bool = True):
    """Trace ``fn(*args)`` and return ``(violations, closed_jaxpr)``.

    ``args``: pytrees of arrays / ShapeDtypeStructs (never executed).
    ``tags``: matching pytrees with a string tag spec per leaf — ``""``
    (untagged), ``"env:<dim>"``, ``"time"``, or a comma-joined combination.
    ``scan_bound``: the checked entry points (policies, reward fns, decide
    steps) all execute inside ``lax.scan``/``lax.map`` bodies, so host
    callbacks are flagged at top level too; pass False for a fn that is
    genuinely dispatched outside any loop.
    """
    closed = jax.make_jaxpr(fn)(*args)
    flat_args = jax.tree.leaves(args)
    flat_tags = jax.tree.leaves(tags)
    if len(flat_args) != len(flat_tags):
        raise ValueError("args/tags pytrees do not match: "
                         f"{len(flat_args)} leaves vs {len(flat_tags)} tags")
    in_provs = [_parse_tag(t, int(np.ndim(a) if not hasattr(a, "shape")
                                  else len(a.shape)))
                for a, t in zip(flat_args, flat_tags)]
    ctx = _Ctx(rules, label or getattr(fn, "__name__", "fn"))
    _run(closed.jaxpr, in_provs, ctx, 1 if scan_bound else 0)
    return ctx.violations, closed


def _raise_if(violations, label):
    if violations:
        raise ContractViolation(violations, label)


def _run_to_fixed_point(jaxpr, in_provs, ctx, loop_depth, pairs,
                        max_iter: int = 8):
    """Run ``_run`` with output->input carry links propagated to a tag
    fixed point (``pairs``: (out_idx, in_idx) leaf links).  The same
    mechanism scan bodies use, lifted one level: a decide step / stateful
    policy runs once per window, so tags its carry picks up in step t must
    be visible to the rule checks of step t+1.  ``ctx`` dedups violations
    across re-runs."""
    in_provs = list(in_provs)
    outs = _run(jaxpr, in_provs, ctx, loop_depth)
    for _ in range(max_iter):
        changed = False
        for oi, ii in pairs:
            if oi >= len(outs) or ii >= len(in_provs):
                continue
            old = in_provs[ii]
            new = _fit(outs[oi], len(old.dims))
            merged = Prov(tuple(a | b for a, b in zip(old.dims, new.dims)),
                          old.val | new.val)
            if merged != old:
                changed = True
                in_provs[ii] = merged
        if not changed or not pairs:
            break
        outs = _run(jaxpr, in_provs, ctx, loop_depth)
    return outs


def _check_carry_structure(carry_tree, provs, n_envs, ctx, what="carry"):
    """Fixed-point structural half of ``carry-env-mix``: every carry leaf
    is either env-tagged exactly on its leading dim (a per-env (E, ...)
    row block the mesh shards on dim 0) or fully env-free (identical on
    every shard).  Anything else — env tags on a trailing dim, or an
    env-tagged leaf whose dim 0 isn't E — cannot shard consistently and
    diverges per device."""
    from jax import tree_util as jtu

    flat, _ = jtu.tree_flatten_with_path(carry_tree)
    for (path, leaf), p in zip(flat, provs):
        shape = tuple(getattr(leaf, "shape", ()))
        env_dims = [d for d, t in enumerate(p.dims) if TAG_ENV in t]
        ok = (not env_dims) or (env_dims == [0] and shape
                                and shape[0] == n_envs)
        if not ok:
            ctx.add(
                "carry-env-mix",
                f"{what} leaf '{jtu.keystr(path)}' (shape {shape}) picks "
                f"up env tags on dims {env_dims} across decide steps: a "
                "carry leaf must be env-tagged exactly on dim 0 (a per-env "
                f"(E={n_envs}, ...) block) or fully env-free, or its "
                "sharded and unsharded fixed points diverge",
                "", "")


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def check_policy(model: Callable, n_features: int, n_envs: int = 4, *,
                 rules: Rules = Rules(), label: Optional[str] = None) -> None:
    """Check a policy ``fn((E, F)) -> (E, A)`` against the shard contract."""
    label = label or getattr(model, "name", None) \
        or getattr(model, "__name__", "policy")
    v, _ = check_fn(lambda f: model(f), (_sds((n_envs, n_features)),),
                    ("env:0",), rules=rules, label=label)
    _raise_if(v, f"policy '{label}'")


def check_reward_fn(fn: Callable, n_envs: int, n_features: int,
                    n_actions: int, *, rules: Rules = Rules(),
                    label: str = "custom reward fn") -> None:
    """Check a custom reward ``fn((E,F), (E,A), (E,A)) -> (E,)``."""
    args = (_sds((n_envs, n_features)), _sds((n_envs, n_actions)),
            _sds((n_envs, n_actions)))
    v, closed = check_fn(fn, args, ("env:0", "env:0", "env:0"),
                         rules=rules, label=label)
    out = closed.out_avals[0]
    if tuple(out.shape) != (n_envs,):
        v = list(v) + [Violation(
            rule="reward-shape", primitive="", source="", label=label,
            message=f"returns shape {tuple(out.shape)} for (E={n_envs}, "
                    f"F={n_features}) features; the contract is one reward "
                    "per env row: (E,)")]
    _raise_if(v, label)


def check_reward_terms(terms, n_features: Optional[int] = None,
                       n_actions: Optional[int] = None, n_envs: int = 4, *,
                       rules: Rules = Rules()) -> None:
    """Check every ``custom`` term of a RewardSpec (duck-typed).

    Feature/action counts are unknown at spec construction, so tracing
    retries up a probe-shape ladder when a fn indexes past the probe; a fn
    that cannot be traced at any probe shape is skipped with a warning
    (it will still be checked at true shapes at system construction).
    """
    ladder = ([(n_features, n_actions)]
              if n_features is not None and n_actions is not None
              else [(8, 4), (32, 8), (128, 16)])
    for i, t in enumerate(terms):
        if getattr(t, "kind", None) != "custom" or t.fn is None:
            continue
        label = f"custom reward term #{i}"
        last_exc = None
        for F, A in ladder:
            try:
                check_reward_fn(t.fn, n_envs, F, A, rules=rules, label=label)
                last_exc = None
                break
            except ContractViolation:
                raise
            except Exception as e:   # probe shape too small, etc.
                last_exc = e
        if last_exc is not None:
            warnings.warn(
                f"repro.analysis: could not statically check {label} at "
                f"probe shapes {ladder}: {last_exc!r} — it will be checked "
                "at true shapes at system construction", stacklevel=2)


def check_decide_fns(decide, dstate, n_envs: int, n_features: int, *,
                     rules: Rules = Rules(), label: str = "decide") -> None:
    """Check a :class:`~repro.runtime.predictor.DecideFns` pair as the fused
    scan will run it: ``step`` on a per-window FeatureFrame with the small
    (replay-free) carry, ``bank`` on the stacked transitions + ring.

    Env tags resolve by leaf rank exactly like ``sharding.env_specs``
    (leading dim == E ⇒ env axis); the int32 tick counter carries the
    abs-time tag, so a ``tick.astype(float32)`` anywhere in a custom step
    is caught here.  An elastic decide state (``dstate.active`` is not
    None) auto-enables the ``env-mask-gate`` family: the ``active``/
    ``prev_ok`` leaves enter mask-tagged, and the bank half is traced with
    the (K, E) ``env_mask`` the fused scan hands it.
    """
    from repro.core.frame import FeatureFrame   # lazy: keep import graph flat

    E, F = n_envs, n_features
    elastic = getattr(dstate, "active", None) is not None
    if elastic:
        rules = rules._replace(mask=True)

    def rank_env(x):
        nd = len(getattr(x, "shape", ()))
        return "env:0" if nd > 0 and x.shape[0] == E else ""

    small = dstate._replace(replay=None)
    s_avals = jax.tree.map(
        lambda x: _sds(jnp.shape(x), jnp.asarray(x).dtype), small)
    s_tags = jax.tree.map(rank_env, s_avals)
    if hasattr(s_tags, "_replace") and hasattr(s_tags, "tick"):
        s_tags = s_tags._replace(tick="time")
    if elastic and hasattr(s_tags, "_replace"):
        s_tags = s_tags._replace(active="env:0,mask",
                                 prev_ok="env:0,mask")
    if hasattr(s_tags, "_replace") and hasattr(s_tags, "policy"):
        # policy weights are batch-global: a (F, A) leaf whose F happens to
        # equal E must not be env-tagged (the rank heuristic can't tell),
        # or the policy's own multiply+reduce over F would false-positive
        # as an env reduction
        s_tags = s_tags._replace(
            policy=jax.tree.map(lambda _: "", s_avals.policy))
    frame = FeatureFrame(features=_sds((E, F)), raw=_sds((E, F)),
                         quality=_sds((E,)), tick_time=_sds((E,)))
    f_tags = FeatureFrame("env:0", "env:0", "env:0", "env:0")

    # trace once, then run the rule walk with the state->state carry links
    # propagated to a fixed point: the fused scan feeds step t's new state
    # to step t+1, so tags a recurrent model carry acquires in one window
    # must be visible to the next window's checks (the ``carry-env-mix``
    # structural rule keys on the fixed-point tags)
    closed = jax.make_jaxpr(decide.step)(s_avals, frame)
    state_leaves = jax.tree.leaves(s_avals)
    n_state = len(state_leaves)
    flat_args = jax.tree.leaves((s_avals, frame))
    flat_tags = jax.tree.leaves((s_tags, f_tags))
    in_provs = [_parse_tag(t, len(a.shape))
                for a, t in zip(flat_args, flat_tags)]
    ctx = _Ctx(rules, f"{label}.step")
    # step returns (new_state, outs, transition): the new state's leaves
    # flatten first, aligning 1:1 with the state input leaves
    out_provs = _run_to_fixed_point(
        closed.jaxpr, in_provs, ctx, 1, [(i, i) for i in range(n_state)])
    mcarry = getattr(small, "carry", None)
    n_mcarry = len(jax.tree.leaves(mcarry))
    if rules.env and n_mcarry:
        # the model carry is DecideState's trailing field, so its leaves
        # are the trailing n_mcarry of the state flatten
        _check_carry_structure(mcarry, out_provs[n_state - n_mcarry:n_state],
                               E, ctx, what=f"{label}.step carry")
    _raise_if(ctx.violations, f"{label}.step")

    # bank runs once per batch outside the scan: trace it on a K-stack of
    # the transition rows the traced step actually emits (step returns
    # (new_state, outs, transition) — the transition is the trailing 7
    # flat outputs (obs, actions, reward, next_obs, tick, version,
    # have_prev) by the DecideFns contract)
    K = 3
    trans_flat = closed.out_avals[-7:]
    trans_avals = [_sds((K,) + tuple(a.shape), a.dtype) for a in trans_flat]
    trans_tags = ["env:1" if len(a.shape) > 1 and a.shape[1] == E else ""
                  for a in trans_avals]
    # the tick column (position -3) is int32 abs-time; the version column
    # beside it is an ordinal counter, NOT a time — it may narrow freely
    if trans_flat[-3].dtype == jnp.int32 and trans_flat[-3].ndim == 0:
        trans_tags[-3] = "time"
    replay_avals = jax.tree.map(
        lambda x: _sds(jnp.shape(x), jnp.asarray(x).dtype), dstate.replay)
    r_tags = jax.tree.map(rank_env, replay_avals)
    if elastic:
        # trace bank exactly as the elastic fused scan calls it: with the
        # (K, E) per-row validity mask, mask-tagged so structural use of
        # it inside the ring write is caught (it may only land in the
        # ``valid`` column's VALUES)
        m_aval = _sds((K, E), jnp.bool_)
        v, _ = check_fn(
            lambda r, tr, m: decide.bank(r, tuple(tr), env_mask=m),
            (replay_avals, trans_avals, m_aval),
            (r_tags, trans_tags, "env:1,mask"),
            rules=rules, label=f"{label}.bank", scan_bound=False)
    else:
        v, _ = check_fn(lambda r, tr: decide.bank(r, tuple(tr)),
                        (replay_avals, trans_avals), (r_tags, trans_tags),
                        rules=rules, label=f"{label}.bank",
                        scan_bound=False)
    _raise_if(v, f"{label}.bank")


def check_train_step(fn: Callable, params, opt_state, replay, *,
                     label: str = "train_step") -> None:
    """Contract gate for the online policy-update step (run at
    ``OnlineTrainer`` construction).

    The loss MAY reduce over the sampled batch axis — a minibatch mean is
    the whole point — so the env family is off (``Rules(env=False)``).
    What must hold: no absolute-time float32 casts (the replay
    ``tick_idx`` column enters tagged abs-time, so a loss that weights by
    raw tick index is caught; rebase with a subtraction first) and no
    host callbacks anywhere in the update (``scan_bound=True``: the step
    overlaps the fused decide dispatch, and a hidden host sync inside it
    re-serializes serving and training).

    ``fn(params, opt_state, replay, rng)`` is traced on the real
    arguments' shapes/dtypes; nothing executes.
    """
    to_aval = lambda t: jax.tree.map(
        lambda x: _sds(jnp.shape(x), jnp.asarray(x).dtype), t)
    blank = lambda t: jax.tree.map(lambda _: "", t)
    p_avals, o_avals, r_avals = (to_aval(params), to_aval(opt_state),
                                 to_aval(replay))
    r_tags = blank(r_avals)
    if hasattr(r_tags, "_replace") and hasattr(r_tags, "tick_idx"):
        r_tags = r_tags._replace(tick_idx="time")
    rng = _sds((2,), jnp.uint32)
    v, _ = check_fn(fn, (p_avals, o_avals, r_avals, rng),
                    (blank(p_avals), blank(o_avals), r_tags, ""),
                    rules=Rules(env=False), label=label, scan_bound=True)
    _raise_if(v, label)


def check_system(predictor, decide=None, dstate=None, *, sharded: bool,
                 label: str = "PerceptaSystem") -> None:
    """Construction-time gate for ``PerceptaSystem`` (``*_sharded``/fused).

    The env-axis family only binds under the env-sharded dispatches; the
    callback/collective/time families hold for every fused build (the
    decide step is traced into the window scan either way).
    """
    rules = Rules(env=sharded)
    E = predictor.n_envs
    F = predictor.n_features
    A = predictor.action_space.n
    if decide is not None:
        check_decide_fns(decide, dstate, E, F, rules=rules,
                         label=f"{label} fused decide")
    else:
        check_policy(predictor.model, F, n_envs=E, rules=rules)
        check_reward_terms(predictor.reward_spec.terms, n_features=F,
                           n_actions=A, n_envs=E, rules=rules)


def check_builtins(verbose: bool = False) -> int:
    """Check every builtin policy/reward/decide path; returns #fns checked.

    ``make lint`` runs this next to the AST lint so a regression in a
    builtin (or in the checker itself) fails CI, not a user's registration.
    """
    from repro.core.reward import (RewardSpec, RewardTerm, KINDS,
                                   energy_reward_spec, validate_actions)
    from repro.runtime.predictor import ActionSpace, Predictor, linear_policy

    E, F, A = 4, 6, 2
    n = 0

    check_policy(linear_policy(F, A), F, n_envs=E)
    n += 1

    # every builtin term kind, checked through RewardSpec.compute at (E, ...)
    terms = [RewardTerm(k, feature=1, action=0, target=1.0, band=0.5)
             for k in KINDS if k != "custom"]
    terms.append(RewardTerm("custom", fn=lambda f, a, p:
                            -f[:, 1] * jnp.maximum(f[:, 0], 0.0)))
    spec = RewardSpec(tuple(terms))
    check_reward_fn(lambda f, a, p: spec.compute(f, a, p)[0], E, F, A,
                    label="RewardSpec.compute[builtin kinds]")
    n += len(terms)

    espec = energy_reward_spec(price_idx=1, grid_idx=0, temp_idx=0)
    check_reward_fn(lambda f, a, p: espec.compute(f, a, p)[0], E, F, A,
                    label="energy_reward_spec.compute")
    n += 1

    v, _ = check_fn(
        lambda a: validate_actions(a, -jnp.ones((A,)), jnp.ones((A,))),
        (_sds((E, A)),), ("env:0",), label="validate_actions")
    _raise_if(v, "validate_actions")
    n += 1

    pred = Predictor(linear_policy(F, A), espec,
                     ActionSpace(np.full(A, -1.0), np.full(A, 1.0)),
                     E, F, replay_capacity=16)
    check_decide_fns(pred.make_decide_fn(), pred.decide_state(), E, F,
                     label="builtin DecideFns")
    n += 2

    # the elastic masked decide path: active/prev_ok enter mask-tagged and
    # the env-mask-gate family is auto-enabled — the shipped masked step/
    # bank must stay select-only clean
    el_state = pred.decide_state()._replace(
        active=jnp.arange(E) < 2, prev_ok=jnp.zeros((E,), bool))
    check_decide_fns(pred.make_decide_fn(), el_state, E, F,
                     label="builtin elastic DecideFns")
    n += 1

    # every registered policy certifies against the FULL rule catalog
    # (carry fixed point, pallas recursion, param replication) — a registry
    # model that stops certifying fails CI here, not a user's standup
    from repro.analysis.certify import certify_policy
    from repro.runtime.policies import POLICIES
    for key, builder in POLICIES.items():
        certify_policy(builder, name=key, cache_key=("builtin", key))
        n += 1
    if verbose:
        print(f"jaxpr contract check: {n} builtin fns clean")
    return n
