"""R8-R11 — device-contract rules (AST half).

The benches only catch hot-path contract violations at runtime, on a
chip, after a recompile storm or a silent host round-trip has already
eaten the p99.  These rules pin the contracts statically; their twin
half (``analysis/devicecheck.py``) verifies the SAME contracts by
abstract-tracing the real verdict models under ``JAX_PLATFORMS=cpu``
— no device, no model execution, zero runtime cost.

- **R8 recompilation hazards.**  Inside jit-reached code (whole-program
  reachability shared with R4): ``int()/float()/bool()`` on a traced
  parameter concretizes at trace time — the value is baked in and
  every new value retraces; ``jnp.array(0.5)``-style scalar constants
  without ``dtype=`` are weak-typed, and weak types flow through
  comparisons into outputs where they key a NEW executable per caller
  dtype mix.  At jit call boundaries: a ``static_argnums`` argument
  fed a list/dict/set literal is unhashable — every call either
  raises or recompiles.
- **R9 implicit host transfers.**  ``.item()``, host-numpy coercion
  (``np.*``), ``device_get`` and ``block_until_ready`` inside a traced
  function are a trace error or a silent device->host sync.  In the
  dispatch hot-path modules the ONLY sanctioned sync point is the
  fenced ``np.asarray`` readback (BENCH_NOTES r4: block_until_ready
  was observed returning pre-execution AND serializes the
  round) — ``.item()`` / ``block_until_ready`` there is per-entry
  latency hidden from the stage histograms.
- **R10 sharding-spec consistency.**  A ``shard_map``/``pjit`` call
  site's ``in_specs`` arity must match the wrapped function's
  positional signature, and a tuple ``out_specs`` must match the
  function's return-tuple length — today this explodes at first trace
  ON A MESH, i.e. in the multi-chip path the single-chip CI never
  exercises (ROADMAP open item 1 pays for this rule).
- **R11 fused-attribution integrity.**  The PR 5 contract: ``verdicts``
  and ``verdicts_attr`` must consume ONE shared hit-matrix pass.  An
  attr twin that calls the plain twin (or re-runs the hits helper)
  is a second device pass — double hot-path cost that no parity test
  notices because the RESULTS are identical.
- **R16 shape-closure (AST half).**  Every jit dispatch must draw its
  batch axis from the declared bucket universe (``MIN_BUCKET`` pow2
  round-up, ``pack_buckets`` widths, ``MIN_RULE_BUCKET`` tables): an
  allocation whose leading dim comes straight from ``len()`` /
  ``.count`` / ``.shape[0]`` keys a NEW executable per distinct batch
  size — a silent re-trace on the hot path that "no new jit shapes"
  prose cannot prevent.  The abstract-trace twin
  (``devicecheck.check_shape_closure``) proves the same closure on the
  real serving surface.
"""

from __future__ import annotations

import ast
import os

from .callgraph import get_graph
from .core import Finding, call_func_name, terminal_name, unparse
from .rules_jit import jit_reached

_HOT_BASENAMES = {"dispatch.py", "service.py", "shm.py"}
_NP_NAMES = {"np", "numpy"}
_JNP_NAMES = {"jnp", "numpy", "np"}  # jnp aliases checked w/ receiver
_CONCRETIZERS = {"int", "float", "bool"}
_SCALAR_CTORS = {"array", "asarray"}


def _fn_params(fn) -> set[str]:
    a = fn.args
    names = [p.arg for p in list(a.posonlyargs) + list(a.args)
             + list(a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    return {n for n in names if n != "self"}


def _has_dtype(call: ast.Call, n_positional_for_dtype: int) -> bool:
    if any(kw.arg == "dtype" for kw in call.keywords):
        return True
    return len(call.args) >= n_positional_for_dtype


# --- R8 -------------------------------------------------------------------

def _r8_traced_body(sf, fn, qual):
    params = _fn_params(fn) if not isinstance(fn, ast.Lambda) else {
        p.arg for p in fn.args.args
    }
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = call_func_name(node)
        if (isinstance(node.func, ast.Name)
                and name in _CONCRETIZERS
                and node.args
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id in params):
            yield Finding(
                "R8", sf.path, node.lineno, node.col_offset,
                f"{name}() on traced argument "
                f"{node.args[0].id!r} concretizes at trace time: the "
                f"Python scalar is baked into the executable and every "
                f"distinct value triggers a silent recompile (or a "
                f"ConcretizationTypeError on a real tracer)",
                symbol=qual,
            )
        elif (isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in _JNP_NAMES):
            if (name in _SCALAR_CTORS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, (int, float))
                    and not isinstance(node.args[0].value, bool)
                    and not _has_dtype(node, 2)):
                yield Finding(
                    "R8", sf.path, node.lineno, node.col_offset,
                    f"weak-typed scalar constant "
                    f"{unparse(node)}: without dtype= the constant's "
                    f"weak type flows into the outputs, where it keys "
                    f"a separate compiled executable per caller dtype "
                    f"mix — pin the dtype",
                    symbol=qual,
                )
            elif (name == "full"
                    and len(node.args) >= 2
                    and isinstance(node.args[1], ast.Constant)
                    and isinstance(node.args[1].value, (int, float))
                    and not isinstance(node.args[1].value, bool)
                    and not _has_dtype(node, 3)):
                yield Finding(
                    "R8", sf.path, node.lineno, node.col_offset,
                    f"weak-typed fill constant {unparse(node)}: "
                    f"without dtype= the fill value's weak type flows "
                    f"into the outputs and keys per-caller recompiles "
                    f"— pin the dtype",
                    symbol=qual,
                )


def _jit_static_positions(sf):
    """{function name: (static positions, static names)} for ONE file,
    from jax.jit(..., static_argnums=...) wrap sites and
    @partial(jax.jit, static_argnums=...) decorators.  Per-file
    scoping keeps the bare-name call-site match precise: an unrelated
    same-named function in another module must not inherit this
    file's static-arg contract."""
    out: dict[str, tuple[set, set]] = {}

    def record(fname: str, call: ast.Call) -> None:
        nums: set[int] = set()
        names: set[str] = set()
        for kw in call.keywords:
            if kw.arg == "static_argnums":
                vals = (kw.value.elts
                        if isinstance(kw.value, (ast.Tuple, ast.List))
                        else [kw.value])
                for v in vals:
                    if isinstance(v, ast.Constant) and isinstance(
                            v.value, int):
                        nums.add(v.value)
            elif kw.arg == "static_argnames":
                vals = (kw.value.elts
                        if isinstance(kw.value, (ast.Tuple, ast.List))
                        else [kw.value])
                for v in vals:
                    if isinstance(v, ast.Constant) and isinstance(
                            v.value, str):
                        names.add(v.value)
        if nums or names:
            prev = out.get(fname, (set(), set()))
            out[fname] = (prev[0] | nums, prev[1] | names)

    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if (isinstance(dec, ast.Call)
                        and call_func_name(dec) == "partial"
                        and dec.args
                        and "jit" in unparse(dec.args[0])):
                    record(node.name, dec)
        elif isinstance(node, ast.Call) and call_func_name(
                node) == "jit":
            if node.args and isinstance(
                    node.args[0], (ast.Name, ast.Attribute)):
                record(terminal_name(node.args[0]), node)
    return out


_UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
               ast.SetComp)


def _r8_static_args(files):
    for sf in files.values():
        statics = _jit_static_positions(sf)
        if not statics:
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            fname = call_func_name(node)
            got = statics.get(fname)
            if got is None:
                continue
            nums, names = got
            for i, a in enumerate(node.args):
                if i in nums and isinstance(a, _UNHASHABLE):
                    yield Finding(
                        "R8", sf.path, a.lineno, a.col_offset,
                        f"unhashable literal passed for static arg "
                        f"{i} of jitted {fname}(): static args key "
                        f"the compile cache by hash — this call "
                        f"raises (or recompiles) every time; pass a "
                        f"tuple or a hashable config object",
                    )
            for kw in node.keywords:
                if kw.arg in names and isinstance(kw.value, _UNHASHABLE):
                    yield Finding(
                        "R8", sf.path, kw.value.lineno,
                        kw.value.col_offset,
                        f"unhashable literal passed for static arg "
                        f"{kw.arg!r} of jitted {fname}(): static args "
                        f"key the compile cache by hash — this call "
                        f"raises (or recompiles) every time; pass a "
                        f"tuple or a hashable config object",
                    )


def check_r8(files):
    reached, all_lambdas = jit_reached(files)
    emitted: set = set()
    for fi in reached:
        sf = files.get(fi.path)
        if sf is None:
            continue
        for f in _r8_traced_body(sf, fi.node, fi.qual):
            key = (f.path, f.line, f.col)
            if key not in emitted:
                emitted.add(key)
                yield f
    for sf, lam in all_lambdas:
        for f in _r8_traced_body(sf, lam, "<lambda>"):
            key = (f.path, f.line, f.col)
            if key not in emitted:
                emitted.add(key)
                yield f
    for f in _r8_static_args(files):
        key = (f.path, f.line, f.col)
        if key not in emitted:
            emitted.add(key)
            yield f


# --- R9 -------------------------------------------------------------------

_TRANSFER_METHODS = {"item", "block_until_ready", "device_get"}

# numpy dtype-scalar constructors: on a LITERAL they build a typed
# constant that traces device-side for free (the dual host/device
# hash-constant idiom in datapath/pipeline.py) — only a non-constant
# argument makes them a concretization/transfer.
_NP_DTYPE_CTORS = {
    "int8", "int16", "int32", "int64", "uint8", "uint16", "uint32",
    "uint64", "float16", "float32", "float64", "bool_",
}


def _r9_traced_body(sf, fn, qual):
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = call_func_name(node)
        if (isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in _NP_NAMES):
            def _const(a):  # literals incl. signed: np.int32(-163...)
                return isinstance(a, ast.Constant) or (
                    isinstance(a, ast.UnaryOp)
                    and isinstance(a.op, (ast.USub, ast.UAdd))
                    and isinstance(a.operand, ast.Constant)
                )

            if name in _NP_DTYPE_CTORS and all(
                _const(a) for a in node.args
            ):
                continue
            yield Finding(
                "R9", sf.path, node.lineno, node.col_offset,
                f"host-numpy call {unparse(node.func)}() inside a "
                f"traced function: on a tracer this is a "
                f"ConcretizationTypeError; on constants it silently "
                f"pins a host round-trip into every dispatch",
                symbol=qual,
            )
        elif (name in _TRANSFER_METHODS
                and isinstance(node.func, ast.Attribute)):
            yield Finding(
                "R9", sf.path, node.lineno, node.col_offset,
                f"{name}() inside a traced function forces a "
                f"device->host transfer at trace time — the value is "
                f"stale for every later batch and the sync point is "
                f"invisible to the stage histograms",
                symbol=qual,
            )


_POLL_METHODS = {"is_ready", "is_deleted"}


def _r9_spin_poll(path, sf):
    """A ``while`` spinning on device-array readiness (is_ready /
    is_deleted in the loop condition) in a hot-path module: the
    device-future poll twin of R2.2's shared-slot spin — it burns a
    core per outstanding round and hides the sync from the stage
    histograms.  The fenced np.asarray readback (or the completion
    pipeline's batched device_get) is the sanctioned wait."""
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.While):
            continue
        for sub in ast.walk(node.test):
            if (isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in _POLL_METHODS):
                yield Finding(
                    "R9", path, node.lineno, node.col_offset,
                    f"spin-polling {sub.func.attr}() on the dispatch "
                    f"hot path: the readiness loop burns a core per "
                    f"outstanding round and the sync is invisible to "
                    f"the stage histograms — use the fenced "
                    f"np.asarray readback (or the completion "
                    f"pipeline's batched device_get)",
                )
                break


def _r9_hot_path(files):
    """In dispatch hot-path modules, the fenced np.asarray readback is
    the ONE sanctioned sync point; .item() / block_until_ready are
    per-entry host syncs the latency decomposition cannot see."""
    for path, sf in sorted(files.items()):
        if os.path.basename(path) not in _HOT_BASENAMES:
            continue
        yield from _r9_spin_poll(path, sf)
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_func_name(node)
            if name == "block_until_ready" and isinstance(
                    node.func, ast.Attribute):
                yield Finding(
                    "R9", path, node.lineno, node.col_offset,
                    "block_until_ready on the dispatch hot path: "
                    "BENCH_NOTES r4 — it was observed returning "
                    "pre-execution and serializes the round; "
                    "the fenced np.asarray readback is the sanctioned "
                    "sync point",
                )
            elif (name == "item"
                    and isinstance(node.func, ast.Attribute)
                    and not node.args and not node.keywords):
                yield Finding(
                    "R9", path, node.lineno, node.col_offset,
                    ".item() on the dispatch hot path is a per-entry "
                    "device->host sync outside the fenced readback — "
                    "read the whole array once via np.asarray and "
                    "index on host",
                )


def check_r9(files):
    reached, all_lambdas = jit_reached(files)
    emitted: set = set()
    for fi in reached:
        sf = files.get(fi.path)
        if sf is None:
            continue
        for f in _r9_traced_body(sf, fi.node, fi.qual):
            key = (f.path, f.line, f.col)
            if key not in emitted:
                emitted.add(key)
                yield f
    for sf, lam in all_lambdas:
        for f in _r9_traced_body(sf, lam, "<lambda>"):
            key = (f.path, f.line, f.col)
            if key not in emitted:
                emitted.add(key)
                yield f
    for f in _r9_hot_path(files):
        key = (f.path, f.line, f.col)
        if key not in emitted:
            emitted.add(key)
            yield f


# --- R10 ------------------------------------------------------------------

def _spec_len(expr: ast.AST) -> int | None:
    """Arity of an in_specs/out_specs expression: tuple/list literal
    length; None for single specs (broadcast / pytree prefix) or
    anything non-literal."""
    if isinstance(expr, (ast.Tuple, ast.List)):
        return len(expr.elts)
    return None


def _positional_arity(fn) -> tuple[int, bool]:
    """(positional param count, has_varargs)."""
    a = fn.args
    return len(a.posonlyargs) + len(a.args), a.vararg is not None


def _return_tuple_lens(fn) -> set[int] | None:
    """Lengths of tuple-literal returns in fn's OWN body (nested defs
    are their own functions — their returns must not leak in); None
    when any own return is not a tuple literal (arity unknowable
    statically)."""
    lens: set[int] = set()
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        if isinstance(node, ast.Return) and node.value is not None:
            if isinstance(node.value, ast.Tuple):
                lens.add(len(node.value.elts))
            else:
                return None
        stack.extend(ast.iter_child_nodes(node))
    return lens or None


def _shard_sites(sf):
    """Yield (call node, target fn name or None, target fn node or
    None, kind) for shard_map/pjit call sites and partial-decorators."""
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if (isinstance(dec, ast.Call)
                        and call_func_name(dec) == "partial"
                        and dec.args
                        and terminal_name(dec.args[0]) in (
                            "shard_map", "pjit")):
                    yield dec, node.name, node, terminal_name(
                        dec.args[0])
        elif isinstance(node, ast.Call) and call_func_name(node) in (
                "shard_map", "pjit"):
            target = node.args[0] if node.args else None
            yield node, (
                terminal_name(target) if target is not None else None
            ), None, call_func_name(node)


def check_r10(files):
    graph = get_graph(files)
    for path, sf in sorted(files.items()):
        mod = graph.mod_of_path[path]
        for call, tname, tnode, kind in _shard_sites(sf):
            in_specs = out_specs = None
            for kw in call.keywords:
                if kw.arg in ("in_specs", "in_shardings"):
                    in_specs = kw.value
                elif kw.arg in ("out_specs", "out_shardings"):
                    out_specs = kw.value
            if in_specs is None and out_specs is None:
                continue
            # resolve the wrapped function
            fn = tnode
            if fn is None and tname:
                for cand in graph.defs.get(mod, {}).get(tname, ()):
                    if cand.cls == "":
                        fn = cand.node
                        break
            if fn is None:
                continue
            n_in = _spec_len(in_specs) if in_specs is not None else None
            if n_in is not None:
                arity, varargs = _positional_arity(fn)
                if not varargs and n_in != arity:
                    yield Finding(
                        "R10", path, call.lineno, call.col_offset,
                        f"{kind} in_specs has {n_in} spec(s) but "
                        f"{fn.name}() takes {arity} positional "
                        f"argument(s) — the mismatch only explodes at "
                        f"first trace on a real mesh (the multi-chip "
                        f"path single-chip CI never runs)",
                        symbol=fn.name,
                    )
            n_out = _spec_len(out_specs) if out_specs is not None \
                else None
            if n_out is not None:
                lens = _return_tuple_lens(fn)
                if lens is not None and lens != {n_out}:
                    got = sorted(lens)
                    yield Finding(
                        "R10", path, call.lineno, call.col_offset,
                        f"{kind} out_specs has {n_out} spec(s) but "
                        f"{fn.name}() returns tuple(s) of length "
                        f"{got} — sharded outputs would be mis-"
                        f"assembled (or the trace explodes) on a "
                        f"real mesh",
                        symbol=fn.name,
                    )


# --- R11 ------------------------------------------------------------------

def _callee_names(fn) -> list[str]:
    out = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            out.append(call_func_name(node))
    return out


def _hits_callees(names: list[str]) -> set[str]:
    return {n for n in names if "hits" in n}


def check_r11(files):
    for path, sf in sorted(files.items()):
        # (plain fn, attr fn) twin pairs: module-level X / X_attr.
        mod_fns: dict[str, ast.AST] = {}
        for node in sf.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                mod_fns[node.name] = node
        for name, fn in sorted(mod_fns.items()):
            if not name.endswith("_attr"):
                continue
            plain = mod_fns.get(name[: -len("_attr")])
            if plain is None:
                continue
            attr_calls = _callee_names(fn)
            plain_name = name[: -len("_attr")]
            plain_hits = _hits_callees(_callee_names(plain))
            attr_hits = _hits_callees(attr_calls)
            if plain_name in attr_calls:
                yield Finding(
                    "R11", path, fn.lineno, fn.col_offset,
                    f"{name}() calls {plain_name}(): a SECOND device "
                    f"pass for attribution — the contract is one "
                    f"shared hit-matrix pass consumed by both the "
                    f"verdict reduction and the argmax (PR 5's fused "
                    f"design); the parity tests cannot see the "
                    f"doubled cost because the results are identical",
                    symbol=name,
                )
            elif plain_hits and attr_hits and not (
                    plain_hits & attr_hits):
                yield Finding(
                    "R11", path, fn.lineno, fn.col_offset,
                    f"{name}() consumes hit pass {sorted(attr_hits)} "
                    f"but {plain_name}() consumes "
                    f"{sorted(plain_hits)} — the twins must share ONE "
                    f"hit-matrix helper or verdict and attribution "
                    f"can drift apart (and each pays its own device "
                    f"pass)",
                    symbol=name,
                )
            elif attr_hits:
                shared = attr_hits & plain_hits
                for h in sorted(shared):
                    if attr_calls.count(h) > 1:
                        yield Finding(
                            "R11", path, fn.lineno, fn.col_offset,
                            f"{name}() invokes the shared hit pass "
                            f"{h}() {attr_calls.count(h)} times — a "
                            f"second device pass for attribution; "
                            f"compute the hit matrix once and feed "
                            f"both reductions",
                            symbol=name,
                        )

# --- R16 ------------------------------------------------------------------

# Dispatch boundaries whose array arguments must carry bucketed batch
# axes (the service's jit seams).
_DISPATCH_NAMES = {"_model_call_attr", "_gathered_call"}
_JIT_WRAPPERS = {"jit", "pjit", "_jit_for"}

_ALLOC_NAMES = {"zeros", "empty", "ones", "full"}

_BUCKET_TEXT = ("bucket", "BUCKET", "pow2", "next_pow")


def _doubled_in_while(fn, name: str) -> bool:
    """True when ``name`` is the target of the pow2 round-up idiom:
    ``while name < n: name *= 2`` (or ``<<=``) anywhere in fn."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.While):
            continue
        for sub in ast.walk(node):
            if (isinstance(sub, ast.AugAssign)
                    and isinstance(sub.target, ast.Name)
                    and sub.target.id == name
                    and isinstance(sub.op, (ast.Mult, ast.LShift))):
                return True
    return False


def _dim_class(expr, assigns, fn, depth: int = 0):
    """'bucket' | 'raw' | None for a batch-dim expression: bucket-
    derived dims come from the MIN_BUCKET family / pow2 round-ups /
    shifts; raw dims come straight from len()/.count/.shape[0]/sum().
    Anything unprovable stays None (precision over recall)."""
    if depth > 6:
        return None
    if isinstance(expr, ast.Constant):
        return "bucket" if isinstance(expr.value, int) else None
    if isinstance(expr, ast.Name):
        if _doubled_in_while(fn, expr.id):
            return "bucket"
        rhs = assigns.get(expr.id)
        if rhs is not None and rhs is not expr:
            return _dim_class(rhs, assigns, fn, depth + 1)
        return None
    if isinstance(expr, ast.Attribute):
        if any(t in expr.attr for t in _BUCKET_TEXT):
            return "bucket"
        if expr.attr == "count":
            return "raw"
        return None
    if isinstance(expr, ast.Subscript):
        v = expr.value
        if isinstance(v, ast.Attribute) and v.attr == "shape":
            return "raw"
        return None
    if isinstance(expr, ast.Call):
        name = call_func_name(expr)
        if any(t in name for t in _BUCKET_TEXT):
            return "bucket"
        if name in ("len", "sum"):
            return "raw"
        if name in ("int", "max", "min"):
            for a in expr.args:
                got = _dim_class(a, assigns, fn, depth + 1)
                if got is not None:
                    return got
        return None
    if isinstance(expr, ast.BinOp):
        if isinstance(expr.op, ast.LShift):
            return "bucket"
        left = _dim_class(expr.left, assigns, fn, depth + 1)
        right = _dim_class(expr.right, assigns, fn, depth + 1)
        if "raw" in (left, right):
            return "raw"
        if "bucket" in (left, right):
            return "bucket"
        return None
    return None


def _r16_fn(sf, fn, qual):
    from .core import local_assignments

    assigns = local_assignments(fn)
    # Names bound to jit-wrapped callables: fn = jax.jit(f)
    jit_names = {
        name for name, rhs in assigns.items()
        if isinstance(rhs, ast.Call)
        and call_func_name(rhs) in _JIT_WRAPPERS
    }
    # Allocations by local name: data = np.zeros((X, W), ...)
    allocs: dict[str, ast.Call] = {}
    for name, rhs in assigns.items():
        if (isinstance(rhs, ast.Call)
                and call_func_name(rhs) in _ALLOC_NAMES
                and rhs.args
                and isinstance(rhs.args[0], ast.Tuple)
                and rhs.args[0].elts):
            allocs[name] = rhs

    def dispatch_call(node: ast.Call) -> bool:
        name = call_func_name(node)
        if name in _DISPATCH_NAMES:
            return True
        if isinstance(node.func, ast.Name) and node.func.id in jit_names:
            return True
        # jit(f)(...) inline
        if isinstance(node.func, ast.Call) and call_func_name(
                node.func) in _JIT_WRAPPERS:
            return True
        return False

    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node is not fn:
                continue
        if not isinstance(node, ast.Call) or not dispatch_call(node):
            continue
        for a in list(node.args) + [kw.value for kw in node.keywords]:
            alloc = None
            if isinstance(a, ast.Name) and a.id in allocs:
                alloc = allocs[a.id]
            elif (isinstance(a, ast.Call)
                    and call_func_name(a) in _ALLOC_NAMES
                    and a.args and isinstance(a.args[0], ast.Tuple)
                    and a.args[0].elts):
                alloc = a
            if alloc is None:
                continue
            dim0 = alloc.args[0].elts[0]
            if _dim_class(dim0, assigns, fn) == "raw":
                yield Finding(
                    "R16", sf.path, alloc.lineno, alloc.col_offset,
                    f"unbucketed batch axis ({unparse(dim0)}) feeds "
                    f"the jit dispatch {call_func_name(node)}(): "
                    f"every distinct batch size keys a NEW compiled "
                    f"executable — a silent re-trace per size on the "
                    f"hot path, outside the declared shape-closure "
                    f"universe; round the axis up to the power-of-two "
                    f"bucket (MIN_BUCKET floor, pack_buckets widths)",
                    symbol=qual,
                )


def check_r16(files):
    from .core import walk_functions

    emitted: set = set()
    for path, sf in sorted(files.items()):
        for fn, qual, _cls in walk_functions(sf.tree):
            for f in _r16_fn(sf, fn, qual):
                key = (f.path, f.line, f.col)
                if key not in emitted:
                    emitted.add(key)
                    yield f
