"""Abstract evaluation + hook plumbing for the static analyzer.

Tracing here is pure abstract evaluation: the target runs once under
``jax.make_jaxpr`` on ``ShapeDtypeStruct`` inputs — no device execution,
no weights moved — while three hook families record what the lint passes
need:

- **op records** — ``framework.tape.apply`` calls the analysis hook for
  every dispatched op (name, input shapes/dtypes, active AMP cast, call
  site), giving the AMP and promotion-drift passes a pre-promotion view
  the post-promotion jaxpr can't reconstruct.
- **host syncs** — ``framework.tensor`` host-interop methods
  (``.numpy()``, ``.item()``, ``float()``, ``bool()``…) called on a
  *tracer* route through the hook, which records the violation and
  returns a shape-correct dummy so the trace runs to completion — a
  would-be runtime crash becomes a static diagnostic.
- **collectives** — the eager ``distributed.collective`` API and the
  in-jit ``prims`` wrappers record (op, group, dtype, shape) into a
  per-rank ledger; ``env.get_rank`` is simulated per rank so Python-level
  rank branches diverge exactly as they would on a real mesh.
"""
from __future__ import annotations

import contextlib
import os
import sys
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.extend.core as jex_core
import jax.numpy as jnp

from ..framework import tape as tape_mod
from ..framework import tensor as tensor_mod
from ..framework.tensor import Tensor

_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_STDLIB = os.path.dirname(os.__file__)
# in-package dirs whose frames are machinery, not anchors; models/ and
# vision/ stay eligible so model-zoo findings anchor inside the model
_SKIP_SUBDIRS = tuple(
    os.path.join(_PKG_ROOT, d) + os.sep
    for d in ("framework", "analysis", "ops", "nn", "jit", "amp",
              "static", "distributed", "incubate", "profiler",
              "observability", "hapi", "io", "utils"))


def _map_dy2static(fn):
    """Translate a converted-code frame filename ("<dy2static:...>") to
    the callee's ORIGINAL source file, or None. Line numbers need no
    translation — ast_transform offsets the tree to match the file."""
    if not fn.startswith("<dy2static"):
        return None
    from ..jit.dy2static.transformer import SOURCE_FILE_MAP
    return SOURCE_FILE_MAP.get(fn)


def callsite():
    """(file, line) of the innermost frame that is user code — outside
    paddle_tpu internals, jax, and the stdlib. Frame-walk, not
    traceback.extract_stack: this runs once per traced op. Frames of
    transitively-converted callees (dy2static capture) attribute to the
    callee's original file/line through the conversion source map."""
    f = sys._getframe(1)
    while f is not None:
        fn = f.f_code.co_filename
        mapped = _map_dy2static(fn)
        if mapped is not None:
            if not mapped.startswith(_SKIP_SUBDIRS):
                return mapped, f.f_lineno
            f = f.f_back
            continue
        # normalize: modules imported via a relative sys.path entry carry
        # "/repo/./pkg/..." co_filenames that break the prefix match
        fn = os.path.normpath(fn) if not fn.startswith("<") else fn
        if not (fn.startswith("<")
                or "/jax/" in fn or "/jaxlib/" in fn
                or "site-packages" in fn
                or fn.startswith(_STDLIB)
                or fn.startswith(_SKIP_SUBDIRS)):
            return fn, f.f_lineno
        f = f.f_back
    return None, None


@dataclass
class OpRecord:
    name: str
    # per-arg: ("T"|"A"|"P"|"O", dtype-or-type str, shape tuple or None)
    ins: list
    amp_mode: str | None   # "white" | "black" | None
    file: str | None
    line: int | None


@dataclass
class HostSync:
    kind: str              # numpy | item | tolist | float | int | bool
    shape: tuple
    dtype: str
    file: str | None
    line: int | None
    rank: int = 0


@dataclass
class CollectiveRecord:
    op: str
    group: str
    dtype: str | None
    shape: tuple | None
    file: str | None
    line: int | None
    peer: int | None = None   # p2p ops: dst (isend/send) / src (irecv/recv)
    # wire compression (int8/bf16) — METADATA, deliberately excluded from
    # key(): a compressed all_reduce and its uncompressed twin are the
    # SAME logical collective, so rank branches that differ only in
    # compression must not read as PTCC schedule divergence. The cost
    # pass reads it to price the compressed wire bytes.
    wire_dtype: str | None = None

    # p2p ops are point-to-point, not SPMD-lockstep: the consistency pass
    # matches them pairwise instead of positionally
    P2P_OPS = ("isend", "irecv", "send", "recv")

    @property
    def is_p2p(self):
        return self.op in self.P2P_OPS

    def key(self):
        return (self.op, self.group, self.dtype, self.shape)

    def __str__(self):
        peer = f", peer={self.peer}" if self.peer is not None else ""
        return (f"{self.op}(group={self.group}, dtype={self.dtype}, "
                f"shape={list(self.shape) if self.shape is not None else '?'}"
                f"{peer})")


@dataclass
class AnalysisContext:
    """Everything the lint passes can look at for one target."""

    target: object = None
    target_name: str = "<target>"
    target_kind: str = "callable"   # callable|layer|to_static|program|train_step
    example_inputs: tuple = ()
    op_records: list = field(default_factory=list)
    host_syncs: list = field(default_factory=list)
    ledgers: dict = field(default_factory=dict)   # rank -> [CollectiveRecord]
    rank_sensitive: bool = False
    jaxpr: object = None            # ClosedJaxpr of the abstract trace
    program: object = None          # static.Program target
    fetches: list = field(default_factory=list)
    source_fns: list = field(default_factory=list)  # fns for the AST pre-pass
    # ORIGINAL callables the dy2static capture layer converted (cache hit
    # or miss) during this trace — fed to the AST pre-pass so hostsync
    # findings in transitively-converted callees attribute to their real
    # file/line
    converted_fns: list = field(default_factory=list)
    static_function: object = None  # jit.api.StaticFunction target
    world_size: int = 1
    trace_error: str | None = None
    # --- cost / memory / donation model inputs & outputs ---
    in_divisors: list = field(default_factory=list)  # per-invar device split
    donated_invars: list = field(default_factory=list)  # per-invar donation
    axis_sizes: dict = field(default_factory=dict)   # mesh axis -> size
    chip: dict | None = None        # roofline constants override
    hbm_budget_bytes: float | None = None   # PTMM001 gate
    train_step: object = None       # fleet train-step target (donation pass)
    cost_summary: object = None     # set by the cost pass
    memory_estimate: object = None  # set by the memory pass


def _describe_arg(a):
    if isinstance(a, Tensor):
        v = a._value
        return ("T", str(np.dtype(v.dtype)), tuple(v.shape))
    if isinstance(a, (jax.Array, jax.core.Tracer)):
        return ("A", str(np.dtype(a.dtype)), tuple(a.shape))
    if isinstance(a, np.ndarray) or isinstance(a, np.generic):
        return ("A", str(np.asarray(a).dtype), tuple(np.shape(a)))
    if isinstance(a, bool):
        return ("O", "bool", None)
    if isinstance(a, (int, float, complex)):
        return ("P", type(a).__name__, None)
    return ("O", type(a).__name__, None)


class TraceRecorder:
    """Per-(target, rank) recording sink wired into the framework hooks."""

    def __init__(self, ctx: AnalysisContext, rank: int = 0,
                 record_ops: bool = True):
        self.ctx = ctx
        self.rank = rank
        self.record_ops = record_ops
        self.ledger: list[CollectiveRecord] = []
        self._bool_sites: dict = {}
        ctx.ledgers[rank] = self.ledger

    # -- tape hook ------------------------------------------------------
    def on_op(self, name, args, amp_cast):
        if not self.record_ops:
            return
        file, line = callsite()
        self.ctx.op_records.append(OpRecord(
            name, [_describe_arg(a) for a in args],
            getattr(amp_cast, "mode", None), file, line))

    # -- host-sync hook -------------------------------------------------
    def on_host_sync(self, kind, t):
        v = t._value
        shape = tuple(v.shape)
        dtype = np.dtype(v.dtype)
        file, line = callsite()
        if kind == "bool":
            # True once per call site, then False: an `if` explores its
            # taken branch, and a tensor-dependent `while` terminates
            # after one recorded iteration instead of spinning the
            # trace forever on the dummy True
            n = self._bool_sites.get((file, line), 0)
            self._bool_sites[(file, line)] = n + 1
            if n == 0:
                self.ctx.host_syncs.append(
                    HostSync(kind, shape, str(dtype), file, line,
                             self.rank))
            return n == 0
        self.ctx.host_syncs.append(
            HostSync(kind, shape, str(dtype), file, line, self.rank))
        if kind == "numpy":
            return np.zeros(shape, dtype)
        if kind == "tolist":
            return np.zeros(shape, dtype).tolist()
        if kind == "item":
            return np.zeros((), dtype).item()
        if kind == "float":
            return 0.0
        return 0  # int

    # -- env rank hook --------------------------------------------------
    def on_get_rank(self, group=None):
        self.ctx.rank_sensitive = True
        return self.rank

    # -- eager collective hooks (distributed/collective.py) -------------
    def _record(self, op, v=None, group=None, peer=None, wire_dtype=None):
        file, line = callsite()
        dtype = shape = None
        if v is not None and hasattr(v, "_value"):
            v = v._value
        if v is not None and hasattr(v, "dtype"):
            dtype, shape = str(np.dtype(v.dtype)), tuple(np.shape(v))
        rec = CollectiveRecord(op, _group_desc(group), dtype, shape,
                               file, line, peer=peer,
                               wire_dtype=wire_dtype)
        self.ledger.append(rec)
        return rec

    def eager_collective(self, op, tensor=None, group=None, peer=None,
                         wire_dtype=None):
        """Record one eager collective; result is the input unchanged
        (abstract semantics: same shape/dtype on every rank)."""
        self._record(op, tensor, group, peer=peer, wire_dtype=wire_dtype)
        return tensor

    def eager_gather(self, op, tensor, group=None, wire_dtype=None):
        self._record(op, tensor, group, wire_dtype=wire_dtype)
        n = self._group_size(group)
        return [tensor] * n

    def _group_size(self, group):
        n = getattr(group, "nranks", None)
        return int(n) if n else max(int(self.ctx.world_size), 1)

    # -- in-jit prims hooks ---------------------------------------------
    def _axis_size(self, axis_name):
        try:
            from ..distributed.mesh import get_global_mesh
            m = get_global_mesh()
            if m is not None:
                axes = ((axis_name,) if isinstance(axis_name, str)
                        else tuple(axis_name))
                n = 1
                for a in axes:
                    n *= int(m.shape[a])
                return n
        except Exception:
            pass
        return max(int(self.ctx.world_size), 1)

    def record_prim(self, name, x=None, axis_name=None, *args, **kw):
        """Record an in-jit collective prim and return an abstractly
        shape-correct stand-in (no mesh axis needed). Compressed
        variants (``*_q``) record under their base op name — wire dtype
        is metadata, not collective identity — so compressed and
        uncompressed schedules compare equal in the PTCC passes."""
        n = self._axis_size(axis_name)
        if name == "axis_index":
            self.ctx.rank_sensitive = True
            return jnp.asarray(self.rank % max(n, 1), jnp.int32)
        if name == "axis_size":
            return n
        wire = None
        if name.endswith("_q"):
            name = name[:-2]
            wire = kw.pop("wire", "int8")
        self._record(name, x, group=f"axis:{axis_name}", wire_dtype=wire)
        if name == "c_allreduce_sum" and (
                kw.get("residual") is not None
                or kw.get("error_feedback")):
            # EF form returns (reduced, new_residual)
            res = kw.get("residual")
            if res is None:
                res = jnp.zeros(x.shape, jnp.float32)
            return x, res

        def arg(pos, key, default):
            if key in kw:
                return kw[key]
            return args[pos] if len(args) > pos else default

        if name == "c_allgather":
            axis = arg(0, "axis", 0)
            if arg(1, "tiled", True):
                return jnp.concatenate([x] * n, axis=axis)
            return jnp.stack([x] * n, axis=axis)
        if name == "c_concat":
            return jnp.concatenate([x] * n, axis=x.ndim - 1)
        if name == "c_split":
            k = x.shape[-1] // n
            return jax.lax.slice_in_dim(x, 0, k, axis=x.ndim - 1)
        if name == "c_reducescatter":
            axis = arg(0, "axis", 0)
            k = x.shape[axis] // n
            return jax.lax.slice_in_dim(x, 0, k, axis=axis)
        if name == "all_to_all":
            split = arg(0, "split_axis", 0)
            concat = arg(1, "concat_axis", 0)
            if split == concat:
                return x
            k = x.shape[split] // n
            y = jax.lax.slice_in_dim(x, 0, k, axis=split)
            return jnp.concatenate([y] * n, axis=concat)
        # reductions / ppermute / broadcast: shape-preserving
        return x


def _group_desc(group) -> str:
    if group is None:
        return "default"
    axis = getattr(group, "axis_name", None)
    ranks = getattr(group, "_ranks", None)
    if axis is not None:
        return f"{axis}" + (f"[{list(ranks)}]" if ranks else "")
    return repr(group)


_PRIM_NAMES = (
    "c_allreduce_sum", "c_allreduce_max", "c_allreduce_min", "c_allgather",
    "c_reducescatter", "c_concat", "c_split", "c_broadcast", "all_to_all",
    "ppermute", "axis_index", "axis_size",
    # compressed variants: recorded under their base op name (wire dtype
    # is metadata), so mixing compressed/uncompressed never lints as
    # schedule divergence
    "c_allreduce_sum_q", "c_allgather_q", "c_reducescatter_q",
    "all_to_all_q",
)


@contextlib.contextmanager
def analysis_hooks(recorder: TraceRecorder):
    """Install every analysis hook (tape, tensor, collectives, env rank,
    prims) for the duration of one abstract trace."""
    from ..distributed import collective as coll_mod
    from ..distributed import env as env_mod

    from ..jit.dy2static import capture as capture_mod

    prev_tape = tape_mod.set_analysis_hook(recorder.on_op)
    prev_sync = tensor_mod._host_sync_hook
    tensor_mod._host_sync_hook = recorder.on_host_sync
    prev_coll = coll_mod._set_analysis_recorder(recorder)
    prev_rank = env_mod._analysis_rank_hook
    env_mod._analysis_rank_hook = recorder.on_get_rank
    prev_capture = capture_mod.set_capture_listener(
        lambda orig: recorder.ctx.converted_fns.append(orig))

    prims = coll_mod.prims
    saved_prims = {}
    for name in _PRIM_NAMES:
        saved_prims[name] = getattr(prims, name)

        def make(n):
            if n in ("axis_size", "axis_index"):
                return staticmethod(
                    lambda axis_name: recorder.record_prim(
                        n, axis_name=axis_name))
            return staticmethod(
                lambda x=None, axis_name=None, *a, **kw:
                    recorder.record_prim(n, x, axis_name, *a, **kw))

        setattr(prims, name, make(name))
    try:
        yield
    finally:
        tape_mod.set_analysis_hook(prev_tape)
        tensor_mod._host_sync_hook = prev_sync
        coll_mod._set_analysis_recorder(prev_coll)
        env_mod._analysis_rank_hook = prev_rank
        capture_mod.set_capture_listener(prev_capture)
        for name, fn in saved_prims.items():
            setattr(prims, name, fn)


def as_aval(x):
    """Normalize an example input to a ShapeDtypeStruct (arrays/Tensors)
    or pass it through (python scalars stay static trace constants)."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    if isinstance(x, Tensor):
        v = x._value
        return jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
    if isinstance(x, (jax.Array, np.ndarray, np.generic)):
        return jax.ShapeDtypeStruct(tuple(np.shape(x)), np.asarray(x).dtype
                                    if not hasattr(x, "dtype") else x.dtype)
    return x


def trace_abstract(fn, example_inputs, recorder: TraceRecorder,
                   want_jaxpr: bool = True):
    """Abstractly evaluate ``fn(*example_inputs)`` with hooks installed.

    Returns (jaxpr | None, error | None). Tensor/array inputs become
    tracers (wrapped in Tensor before fn sees them); python scalars are
    baked as trace constants — exactly the to_static contract.
    """
    from ..framework import random as random_mod

    norm = [as_aval(a) for a in example_inputs]
    array_idx = [i for i, a in enumerate(norm)
                 if isinstance(a, jax.ShapeDtypeStruct)]
    avals = [norm[i] for i in array_idx]
    # concrete key, materialized OUTSIDE the trace: without the guard,
    # in-model RNG draws (dropout, gshard gate noise) would advance the
    # process-global generator with a tracer — a leaked key that poisons
    # every later eager draw. fold_in (not next_key): the analysis must
    # not CONSUME from the ambient stream — validate=True would silently
    # shift a seeded run's randomness — and every simulated rank must
    # trace under the SAME key, or key-dependent control flow would
    # register as false cross-rank divergence
    rng_key = jax.random.fold_in(random_mod.get_rng_state(), 0)

    def run(*tvals):
        full = list(norm)
        for i, v in zip(array_idx, tvals):
            full[i] = Tensor(v)
        with tape_mod.no_grad_guard(), random_mod.rng_guard(rng_key):
            out = fn(*full)
        leaves = jax.tree_util.tree_leaves(
            out, is_leaf=lambda t: isinstance(t, Tensor))
        vals = [l._value if isinstance(l, Tensor) else l for l in leaves]
        vals = [v for v in vals
                if isinstance(v, (jax.Array, jax.core.Tracer))]
        return vals if vals else 0

    try:
        with analysis_hooks(recorder):
            if want_jaxpr:
                return jax.make_jaxpr(run)(*avals), None
            # per-rank re-traces only need the hooks to fire (collective
            # ledgers, host syncs): skip jaxpr construction
            jax.eval_shape(run, *avals)
            return None, None
    except Exception as e:  # degrade: passes that need no trace still run
        return None, f"{type(e).__name__}: {e}"


def iter_eqns(jaxpr):
    """Every eqn in a (Closed)Jaxpr including nested sub-jaxprs (pjit,
    scan, cond, remat...)."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def _sub_jaxprs(v):
    if isinstance(v, jex_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jex_core.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _sub_jaxprs(x)


def eqn_site(eqn):
    """Best-effort (file, line) for a jaxpr eqn from its source_info."""
    try:
        tb = eqn.source_info.traceback
        for fr in reversed(tb.frames):
            fn = getattr(fr, "file_name", None) or getattr(fr, "filename", "")
            line = getattr(fr, "line_num", None) or getattr(fr, "lineno", 0)
            mapped = _map_dy2static(fn)
            if mapped is not None:
                if not mapped.startswith(_SKIP_SUBDIRS):
                    return mapped, line
                continue
            fn = os.path.normpath(fn) if not fn.startswith("<") else fn
            if not (fn.startswith("<") or "/jax/" in fn
                    or "site-packages" in fn or fn.startswith(_STDLIB)
                    or fn.startswith(_SKIP_SUBDIRS)):
                return fn, line
    except Exception:
        pass
    return None, None
