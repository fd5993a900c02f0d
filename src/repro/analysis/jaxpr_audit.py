"""Jaxpr-level static auditor for the repo's public entry points.

Every registered entry point is traced with ``jax.make_jaxpr`` on a
canonical tiny problem (tracing compiles nothing and runs nothing) and
the resulting ClosedJaxpr is walked — recursively through ``scan`` /
``while`` / ``cond`` / ``pjit`` / ``pallas_call`` sub-jaxprs — for four
violation classes:

``host-sync``
    A host-callback / debug primitive inside a traced hot path
    (``pure_callback``, ``io_callback``, ``debug_print``, ...): each one
    is a device->host round trip per step.

``dtype-narrow`` / ``weak-promo``
    An implicit ``convert_element_type`` between float dtypes.  Narrowing
    (f64 -> f32 on an x64 problem, f32 -> f16 anywhere) silently truncates
    precision; widening above the problem dtype (f32 -> f64 under
    JAX_ENABLE_X64) is Python-scalar / NumPy-scalar contamination — a
    strong float64 constant leaked into f32 arithmetic.  Weak-typed
    operands are exempt (a weak ``0.0`` adapting to the array dtype is
    JAX's intended semantics).  Entries may declare ``allow_dtypes`` for
    intentional storage casts (the bf16 quantized-serving anchors are
    storage-only by contract).

``const-leak`` / ``grid-recompile``
    The zero-recompile claims, proven statically.  A swept parameter
    (fault rate, pruning ``tau``, forgetting ``beta``) is traced as a
    function INPUT; the check fails if tracing concretizes it (a
    ``float()`` / ``if rate:`` on the traced value), if the parameter is
    dead in the jaxpr (its value was baked into a static position or
    closure constant), or if a sentinel grid value shows up as a jaxpr
    literal.  ``grid-recompile`` additionally compares the jit cache
    signature — pytree structure + abstract values — of the full call
    across a grid of parameter values: equal signatures mean ONE compiled
    program serves the whole grid, without executing a sweep.

``alive-dead`` / ``alive-scatter``
    Liveness-gate threading.  The entry's liveness mask is tainted and
    the taint is propagated through the jaxpr (with fixpoints over scan /
    while carries): if no output depends on the mask, the gate was
    dropped (``alive-dead``); if a scatter-family write's indices AND
    updates are both untainted, a table write bypasses the gate
    (``alive-scatter``) — dead rows could be written as if alive.
"""

from __future__ import annotations

import dataclasses
import functools
from collections.abc import Callable

import numpy as np

import jax
import jax.numpy as jnp

from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

from .report import Finding

# Distinctive sentinel for the swept-parameter checks: if this value is
# found baked into a jaxpr literal/const, the parameter leaked out of the
# traced operand position.
MAGIC = 0.6180339887498949

HOST_SYNC_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "host_callback_call", "outside_call", "infeed", "outfeed",
})

# Value-level write primitives into fixed-shape tables.  invars[0] is the
# written-into operand; the gate must reach the indices or the updates.
SCATTER_PRIMITIVES = frozenset({
    "scatter", "scatter-add", "scatter-mul", "scatter-min", "scatter-max",
    "dynamic_update_slice",
})


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _jaxprs_of(v):
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _jaxprs_of(x)


def iter_eqns(jaxpr: Jaxpr):
    """All eqns of ``jaxpr`` and (recursively) of every sub-jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _jaxprs_of(v):
                yield from iter_eqns(sub)


# ---------------------------------------------------------------------------
# taint propagation
# ---------------------------------------------------------------------------


def _taint(jaxpr: Jaxpr, in_taint, on_eqn=None):
    """Forward data-flow: which jaxpr outputs depend on tainted invars.

    ``on_eqn(eqn, input_taints)`` is called once per eqn (after loop
    carries reach their fixpoint, so a write gated through the carry is
    never misreported as untainted).
    """
    env: dict = {}
    for v, t in zip(jaxpr.invars, in_taint):
        env[v] = env.get(v, False) or bool(t)
    for v in jaxpr.constvars:
        env.setdefault(v, False)

    def read(a):
        return False if isinstance(a, Literal) else env.get(a, False)

    for eqn in jaxpr.eqns:
        ts = [read(x) for x in eqn.invars]
        if on_eqn is not None:
            on_eqn(eqn, ts)
        out_ts = _eqn_taint(eqn, ts, on_eqn)
        if out_ts is None or len(out_ts) != len(eqn.outvars):
            out_ts = [any(ts)] * len(eqn.outvars)
        for v, t in zip(eqn.outvars, out_ts):
            env[v] = bool(t)
    return [read(v) for v in jaxpr.outvars]


def _eqn_taint(eqn, ts, on_eqn):
    name = eqn.primitive.name
    params = eqn.params
    if name == "scan":
        inner = params["jaxpr"].jaxpr
        nc, ncar = params["num_consts"], params["num_carry"]
        consts, carry, xs = ts[:nc], ts[nc:nc + ncar], ts[nc + ncar:]
        for _ in range(ncar + 2):  # carry-feedback fixpoint
            res = _taint(inner, consts + carry + xs)
            new_carry = [a or b for a, b in zip(carry, res[:ncar])]
            if new_carry == carry:
                break
            carry = new_carry
        res = _taint(inner, consts + carry + xs, on_eqn)
        return [a or b for a, b in zip(carry, res[:ncar])] + res[ncar:]
    if name == "while":
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        body = params["body_jaxpr"].jaxpr
        cconsts, bconsts, carry = ts[:cn], ts[cn:cn + bn], ts[cn + bn:]
        for _ in range(len(carry) + 2):
            res = _taint(body, bconsts + carry)
            new_carry = [a or b for a, b in zip(carry, res)]
            if new_carry == carry:
                break
            carry = new_carry
        _taint(body, bconsts + carry, on_eqn)
        _taint(params["cond_jaxpr"].jaxpr, cconsts + carry, on_eqn)
        return carry
    if name == "cond":
        outs = [
            _taint(br.jaxpr, ts[1:], on_eqn) for br in params["branches"]
        ]
        return [ts[0] or any(col) for col in zip(*outs)]
    if name == "pallas_call":
        inner = params.get("jaxpr")
        if inner is not None:
            ij = inner.jaxpr if isinstance(inner, ClosedJaxpr) else inner
            k = len(ij.invars)
            # kernel invars are [input refs..., output refs..., scratch]
            _taint(ij, (ts + [False] * k)[:k], on_eqn)
        return None  # conservative: any(ts) on all outputs
    for key in ("jaxpr", "call_jaxpr"):  # pjit / remat / custom_* / shard_map
        sub = params.get(key)
        if isinstance(sub, (Jaxpr, ClosedJaxpr)):
            ij = sub.jaxpr if isinstance(sub, ClosedJaxpr) else sub
            if len(ij.invars) == len(ts):
                return _taint(ij, ts, on_eqn)
    return None


# ---------------------------------------------------------------------------
# the checks
# ---------------------------------------------------------------------------


def _check_host_sync(name: str, closed: ClosedJaxpr) -> list[Finding]:
    out = []
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name in HOST_SYNC_PRIMITIVES:
            out.append(Finding(
                "host-sync", name, eqn.primitive.name,
                "host callback primitive in a traced hot path "
                "(one device->host round trip per execution)",
            ))
    return out


def _check_dtype(
    name: str, closed: ClosedJaxpr, trace_dtype, allow: frozenset
) -> list[Finding]:
    out = []
    width = np.dtype(trace_dtype).itemsize
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        aval = eqn.invars[0].aval
        old = np.dtype(aval.dtype)
        new = np.dtype(eqn.params["new_dtype"])
        if old.kind != "f" or new.kind != "f" or old == new:
            continue
        if {old.name, new.name} & allow:
            continue
        # Weak-typed operands (Python-scalar literals like ``0.0`` /
        # ``jnp.inf``) adapt to the array dtype BY DESIGN — that convert
        # is JAX's intended promotion semantics, not contamination.  Only
        # strong wider floats (np.float64 scalars, default-dtype arrays
        # under x64) are findings.
        if getattr(aval, "weak_type", False):
            continue
        if new.itemsize < old.itemsize:
            out.append(Finding(
                "dtype-narrow", name, f"{old.name}->{new.name}",
                f"implicit float narrowing inside the {trace_dtype} trace "
                "— values are silently truncated",
            ))
        elif new.itemsize > width:
            out.append(Finding(
                "weak-promo", name, f"{old.name}->{new.name}",
                f"promotion above the {trace_dtype} problem dtype — a "
                "strong wider-float scalar (np.float64 / pinned literal) "
                "contaminated the arithmetic",
            ))
    return out


def _check_alive(name: str, built, do_scatter: bool) -> list[Finding]:
    fn, args = built.alive
    closed = jax.make_jaxpr(fn)(*args)
    in_t = [i == 0 for i in range(len(closed.jaxpr.invars))]
    findings: list[Finding] = []

    def on_eqn(eqn, ts):
        if (
            do_scatter
            and eqn.primitive.name in SCATTER_PRIMITIVES
            and not any(ts[1:])
        ):
            findings.append(Finding(
                "alive-scatter", name, eqn.primitive.name,
                "table write whose indices and updates are both "
                "independent of the liveness mask — dead rows can be "
                "written as if alive",
            ))

    out_t = _taint(closed.jaxpr, in_t, on_eqn)
    if not any(out_t):
        findings.append(Finding(
            "alive-dead", name, "",
            "no output depends on the liveness mask — the alive gate "
            "is accepted but dropped",
        ))
    return findings


def _is_magic(x) -> bool:
    try:
        arr = np.asarray(x)
    except (TypeError, ValueError):
        return False
    return (
        arr.size >= 1
        and arr.dtype.kind == "f"
        and bool(np.any(np.abs(arr.astype(np.float64) - MAGIC) < 1e-6))
    )


def _check_param(name: str, built) -> list[Finding]:
    try:
        closed = jax.make_jaxpr(built.param)(MAGIC)
    except Exception as exc:  # concretization / static-position errors
        return [Finding(
            "const-leak", name, "untraceable",
            f"tracing with an abstract parameter failed — the value is "
            f"concretized or static, so every grid point recompiles "
            f"({type(exc).__name__}: {str(exc)[:200]})",
        )]
    findings = []
    in_t = [i == 0 for i in range(len(closed.jaxpr.invars))]
    if not any(_taint(closed.jaxpr, in_t)):
        findings.append(Finding(
            "const-leak", name, "dead-param",
            "the swept parameter does not influence any output — its "
            "value was baked in elsewhere (closure constant or static "
            "argument), so the sweep result is stale or recompiles",
        ))
    for eqn in iter_eqns(closed.jaxpr):
        for v in eqn.invars:
            if isinstance(v, Literal) and _is_magic(v.val):
                findings.append(Finding(
                    "const-leak", name, "baked-literal",
                    "the sentinel parameter value appears as a jaxpr "
                    "literal — it was constant-folded instead of traced",
                ))
                return findings
    for c in closed.consts:
        if _is_magic(c):
            findings.append(Finding(
                "const-leak", name, "baked-const",
                "the sentinel parameter value appears as a jaxpr "
                "constant — it was closed over instead of traced",
            ))
            break
    return findings


def _leaf_sig(leaf):
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return (
            tuple(leaf.shape), str(leaf.dtype),
            bool(getattr(leaf, "weak_type", False)),
        )
    return ("weak-pyscalar", type(leaf).__name__)


def _check_grid(name: str, built) -> list[Finding]:
    sigs = []
    for v in built.grid:
        args = built.build_call(v)
        leaves, treedef = jax.tree_util.tree_flatten(args)
        sigs.append((str(treedef), tuple(_leaf_sig(x) for x in leaves)))
    bad = [v for v, s in zip(built.grid, sigs) if s != sigs[0]]
    if bad:
        return [Finding(
            "grid-recompile", name, "",
            f"jit cache signature (pytree structure + avals) changes "
            f"across the value grid at {bad} — each such value compiles "
            f"a separate program",
        )]
    return []


# ---------------------------------------------------------------------------
# entry registry
# ---------------------------------------------------------------------------


class Built:
    """Concrete audit material for one entry point.

    fn/args:     canonical call, traced for host-sync + dtype checks.
    alive:       (fn, args) with the liveness mask as argument 0.
    param:       fn(scalar) for the traced-parameter (const-leak) check.
    grid +
    build_call:  values and v -> call-args-pytree for the one-program
                 cache-signature check.
    """

    def __init__(self, fn=None, args=(), alive=None, param=None,
                 grid=None, build_call=None):
        self.fn, self.args = fn, args
        self.alive = alive
        self.param = param
        self.grid = grid
        self.build_call = build_call


@dataclasses.dataclass
class EntrySpec:
    name: str
    build: Callable[[], Built]
    checks: tuple[str, ...] = ("host-sync", "dtype")
    allow_dtypes: frozenset = frozenset()


def audit_entry(spec: EntrySpec, trace_dtype="float32") -> list[Finding]:
    """Run the spec's checks; findings are deduped by key."""
    built = spec.build()
    findings: list[Finding] = []
    if built.fn is not None and (
        "host-sync" in spec.checks or "dtype" in spec.checks
    ):
        closed = jax.make_jaxpr(built.fn)(*built.args)
        if "host-sync" in spec.checks:
            findings += _check_host_sync(spec.name, closed)
        if "dtype" in spec.checks:
            findings += _check_dtype(
                spec.name, closed, trace_dtype, spec.allow_dtypes
            )
    if built.alive is not None and "alive" in spec.checks:
        findings += _check_alive(
            spec.name, built, do_scatter="alive-scatter" in spec.checks
        )
    if built.param is not None and "param" in spec.checks:
        findings += _check_param(spec.name, built)
    if built.grid is not None and "param" in spec.checks:
        findings += _check_grid(spec.name, built)
    return list({f.key: f for f in findings}.values())


def run_entries(
    entries: list[EntrySpec], trace_dtype="float32"
) -> list[Finding]:
    findings = []
    for spec in entries:
        findings += audit_entry(spec, trace_dtype=trace_dtype)
    return findings


# --- canonical fixture -----------------------------------------------------


@functools.lru_cache(maxsize=4)
def _fixture(dtype_name: str):
    """Tiny canonical problems (batched + single-field), built once per
    dtype.  Only traced — never executed — so size is irrelevant beyond
    exercising every code path (streaming slots, spare rows, coloring)."""
    from types import SimpleNamespace

    from repro.core import (
        Kernel, build_topology, init_state, make_batch_problem,
        make_problem, make_serving_plan, uniform_sensors,
    )

    n, b = 12, 2
    # Dtype-consistent canonical shapes: positions in the trace dtype so
    # churn ops don't round-trip through a mixed-precision topology.
    pos = np.asarray(uniform_sensors(n, seed=0)).astype(dtype_name)
    rng = np.random.default_rng(1)
    ys = (
        np.sin(np.pi * pos[None, :, 0] * np.array([[1.0], [1.7]]))
        + 0.1 * rng.normal(size=(b, n))
    ).astype(dtype_name)
    topo = build_topology(pos, 0.7)
    d_max = int(np.asarray(topo.degrees).max()) + 3
    topo = build_topology(pos, 0.7, d_max=d_max, n_max=n + 2)
    kern = Kernel("rbf", gamma=1.0)
    lam = jnp.full((n,), 0.1, dtype_name)
    prob = make_batch_problem(
        topo, kern, ys, lam, dtype=jnp.dtype(dtype_name), beta=0.9
    )
    sprob = make_problem(
        topo, kern, jnp.asarray(ys[0]), lam, dtype=jnp.dtype(dtype_name)
    )
    fx = SimpleNamespace(
        prob=prob, state=init_state(prob),
        sprob=sprob, sstate=init_state(sprob),
        plan=make_serving_plan(prob, k=2),
        xq=jnp.asarray(
            rng.uniform(-0.9, 0.9, size=(8, 1)), jnp.dtype(dtype_name)
        ),
        key=jax.random.PRNGKey(0),
        dtype=jnp.dtype(dtype_name),
    )
    return fx


def _replace_alive(problem, alive):
    return dataclasses.replace(problem, alive=alive)


def default_entries(dtype_name: str = "float32") -> list[EntrySpec]:
    """The registered public entry points, audited on canonical shapes."""
    import repro.core.faults as faults
    import repro.core.fusion as fusion
    import repro.core.monitor as monitor
    import repro.core.pruning as pruning
    import repro.core.serving as serving
    import repro.core.streaming as streaming
    from repro.core import (
        SNTrainState, colored_sweep, random_sweep, robust_sweep,
        robust_sweep_links, serial_sweep, sharded_sweep, weighted_sweep,
    )
    from repro.kernels import kernel_matvec

    fx = _fixture(dtype_name)
    # Sweep engines carry the scatter-level contract (every z/coef write
    # redirects through the liveness sentinel); streaming/churn ops gate
    # their FINAL state writes on alive but legitimately build temporary
    # factors with alive-independent scatters, so they get the
    # output-taint check only.
    SWEEP = ("host-sync", "dtype", "alive", "alive-scatter")
    STREAM = ("host-sync", "dtype", "alive")
    FULL = SWEEP + ("param",)

    def sweep_entry(name, call, **kw):
        def build():
            def f(alive, z, coef):
                return call(
                    _replace_alive(fx.prob, alive), SNTrainState(z, coef)
                )
            args = (fx.prob.alive, fx.state.z, fx.state.coef)
            return Built(fn=f, args=args, alive=(f, args))
        return EntrySpec(name, build, checks=kw.pop("checks", SWEEP), **kw)

    def simple_entry(name, build_fn_args, checks=("host-sync", "dtype"),
                     **kw):
        def build():
            fn, args = build_fn_args()
            return Built(fn=fn, args=args)
        return EntrySpec(name, build, checks=checks, **kw)

    entries = [
        sweep_entry(
            "sweep.serial", lambda p, s: serial_sweep(p, s, n_sweeps=2)
        ),
    ]
    for engine in ("plan", "onehot", "pallas"):
        entries.append(sweep_entry(
            f"sweep.colored.{engine}",
            lambda p, s, e=engine: colored_sweep(p, s, 2, engine=e),
        ))

    def build_sharded():
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:1]), ("sensors",)
        )
        def f(alive, z, coef):
            return sharded_sweep(
                _replace_alive(fx.prob, alive), SNTrainState(z, coef),
                mesh, n_sweeps=2,
            )
        args = (fx.prob.alive, fx.state.z, fx.state.coef)
        return Built(fn=f, args=args, alive=(f, args))
    entries.append(EntrySpec("sweep.sharded.plan", build_sharded, SWEEP))

    def build_random():
        def f(alive, z, coef, key):
            return random_sweep(
                _replace_alive(fx.sprob, alive), SNTrainState(z, coef),
                key, n_sweeps=2,
            )
        args = (fx.sprob.alive, fx.sstate.z, fx.sstate.coef, fx.key)
        return Built(fn=f, args=args, alive=(f, args))
    entries.append(EntrySpec("sweep.random", build_random, SWEEP))

    def build_weighted():
        w = jnp.full((fx.sprob.n,), 2.0, fx.dtype)
        def f(alive, z, coef):
            return weighted_sweep(
                _replace_alive(fx.sprob, alive), SNTrainState(z, coef),
                w, n_sweeps=2,
            )
        args = (fx.sprob.alive, fx.sstate.z, fx.sstate.coef)
        return Built(fn=f, args=args, alive=(f, args))
    entries.append(EntrySpec("sweep.weighted", build_weighted, SWEEP))

    def build_robust():
        alive_tn = jnp.ones((2, fx.prob.n), bool)
        def f(a, z, coef):
            return robust_sweep(
                fx.prob, SNTrainState(z, coef), a, n_sweeps=2,
                engine="plan",
            )
        args = (alive_tn, fx.state.z, fx.state.coef)
        return Built(fn=f, args=args, alive=(f, args))
    entries.append(EntrySpec("sweep.robust", build_robust, SWEEP))

    def build_robust_links():
        d_max = fx.sprob.nbr_idx.shape[-1]
        links = jnp.ones((2, fx.sprob.n, d_max), bool)
        def f(a, z, coef):
            return robust_sweep_links(
                fx.sprob, SNTrainState(z, coef), a, n_sweeps=2
            )
        args = (links, fx.sstate.z, fx.sstate.coef)
        return Built(fn=f, args=args, alive=(f, args))
    entries.append(EntrySpec("sweep.robust_links", build_robust_links, SWEEP))

    # fault-injected sweeps: rate grid must be one program
    def build_faulty(engine, crash):
        def build():
            mk = lambda r: faults.make_fault_model(
                r, burst=(0.05, 0.5, 0.3), crash=crash,
                dtype=fx.dtype,
            )
            def f(alive, z, coef, r):
                return faults.faulty_sweep(
                    _replace_alive(fx.prob, alive), SNTrainState(z, coef),
                    mk(r), fx.key, n_sweeps=2, engine=engine,
                )
            args = (
                fx.prob.alive, fx.state.z, fx.state.coef,
                jnp.asarray(0.1, fx.dtype),
            )
            return Built(
                fn=f, args=args, alive=(f, args),
                param=lambda r: faults.faulty_sweep(
                    fx.prob, fx.state, mk(r), fx.key, n_sweeps=2,
                    engine=engine,
                ),
                grid=(0.0, 0.1, MAGIC, 0.9),
                build_call=lambda v: (fx.prob, fx.state, mk(v), fx.key),
            )
        return build
    for engine in ("plan", "serial", "pallas"):
        entries.append(EntrySpec(
            f"faults.{engine}", build_faulty(engine, None), FULL
        ))
    entries.append(EntrySpec(
        "faults.crash", build_faulty("plan", (0.1, 0.5)), FULL
    ))

    # streaming: absorb (beta grid must be one program), windows, churn
    def build_absorb():
        x = fx.xq[0]
        y = jnp.asarray(0.3, fx.dtype)
        def with_beta(bv):
            beta = jnp.broadcast_to(
                jnp.asarray(bv, fx.prob.beta.dtype), fx.prob.beta.shape
            )
            return dataclasses.replace(fx.prob, beta=beta)
        def f(alive, z, coef):
            return streaming.absorb(
                _replace_alive(fx.prob, alive), SNTrainState(z, coef),
                0, 3, x, y,
            )
        args = (fx.prob.alive, fx.state.z, fx.state.coef)
        return Built(
            fn=f, args=args, alive=(f, args),
            param=lambda bv: streaming.absorb(
                with_beta(bv), fx.state, 0, 3, x, y
            ),
            grid=(1.0, MAGIC, 0.5),
            build_call=lambda v: (with_beta(v), fx.state, 0, 3, x, y),
        )
    entries.append(EntrySpec(
        "stream.absorb", build_absorb, STREAM + ("param",)
    ))

    def build_absorb_many():
        a = 3
        fields = jnp.zeros((a,), jnp.int32)
        sensors = jnp.arange(a, dtype=jnp.int32)
        xs = jnp.broadcast_to(fx.xq[0], (a,) + fx.xq[0].shape)
        ys = jnp.full((a,), 0.2, fx.dtype)
        def f(alive, z, coef):
            return streaming.absorb_many(
                _replace_alive(fx.prob, alive), SNTrainState(z, coef),
                fields, sensors, xs, ys,
            )
        args = (fx.prob.alive, fx.state.z, fx.state.coef)
        return Built(fn=f, args=args, alive=(f, args))
    entries.append(EntrySpec(
        "stream.absorb_many", build_absorb_many, STREAM
    ))

    def build_add():
        x = jnp.asarray(np.array([0.05]), fx.dtype)
        ys = jnp.full((fx.prob.batch_size,), 0.1, fx.dtype)
        def f(alive, z, coef):
            return streaming.add_sensor(
                _replace_alive(fx.prob, alive), SNTrainState(z, coef),
                x, ys, lam=0.1,
            )
        args = (fx.prob.alive, fx.state.z, fx.state.coef)
        return Built(fn=f, args=args, alive=(f, args))
    entries.append(EntrySpec("stream.add_sensor", build_add, STREAM))

    def build_remove():
        def f(alive, z, coef):
            return streaming.remove_sensor(
                _replace_alive(fx.prob, alive), SNTrainState(z, coef), 2
            )
        args = (fx.prob.alive, fx.state.z, fx.state.coef)
        return Built(fn=f, args=args, alive=(f, args))
    entries.append(EntrySpec("stream.remove_sensor", build_remove, STREAM))

    def build_evict():
        def f(alive, z, coef):
            return streaming.evict_oldest(
                _replace_alive(fx.prob, alive), SNTrainState(z, coef), 0, 3
            )
        args = (fx.prob.alive, fx.state.z, fx.state.coef)
        return Built(fn=f, args=args, alive=(f, args))
    entries.append(EntrySpec("stream.evict_oldest", build_evict, STREAM))

    # serving / fusion: alive gates selection; tau grid is one program
    def build_fuse(engine, compute_dtype=None):
        def build():
            def f(alive, z, coef):
                return fusion.fuse(
                    _replace_alive(fx.prob, alive), SNTrainState(z, coef),
                    fx.xq, "knn", k=2, engine=engine,
                    plan=None if engine == "dense" else fx.plan,
                    compute_dtype=compute_dtype,
                )
            args = (fx.prob.alive, fx.state.z, fx.state.coef)
            return Built(fn=f, args=args, alive=(f, args))
        return build
    entries.append(EntrySpec(
        "serving.knn.plan", build_fuse("plan"),
        ("host-sync", "dtype", "alive"),
    ))
    entries.append(EntrySpec(
        "serving.knn.pallas", build_fuse("pallas"),
        ("host-sync", "dtype", "alive"),
    ))
    entries.append(EntrySpec(
        "serving.knn.quant", build_fuse("pallas", "bfloat16"),
        ("host-sync", "dtype", "alive"),
        allow_dtypes=frozenset({"bfloat16"}),
    ))
    entries.append(EntrySpec(
        "fusion.dense", build_fuse("dense"), ("host-sync", "dtype", "alive"),
    ))

    def build_prune():
        def f(alive, z, coef, tau):
            return pruning.prune_mask(
                _replace_alive(fx.prob, alive), SNTrainState(z, coef),
                energy_tau=tau,
            )
        args = (
            fx.prob.alive, fx.state.z, fx.state.coef,
            jnp.asarray(0.05, fx.dtype),
        )
        return Built(
            fn=f, args=args, alive=(f, args),
            param=lambda t: pruning.prune_mask(
                fx.prob, fx.state, energy_tau=t
            ),
            grid=(0.0, MAGIC, 0.3),
            build_call=lambda v: (
                fx.prob.nbr_mask, fx.prob.alive, fx.state.coef,
                jnp.asarray(v, fx.dtype),
            ),
        )
    entries.append(EntrySpec(
        "pruning.keep", build_prune, ("host-sync", "dtype", "alive", "param")
    ))

    def build_plan_add():
        x = jnp.asarray(np.array([0.05]), fx.plan.centers.dtype)
        return (
            lambda plan_cells, plan_mask: serving.plan_add_sensor(
                dataclasses.replace(
                    fx.plan, cells=plan_cells, cell_mask=plan_mask
                ),
                x, jnp.asarray(5, jnp.int32),
            ),
            (fx.plan.cells, fx.plan.cell_mask),
        )
    entries.append(simple_entry("serving.plan_add", build_plan_add))

    def build_plan_remove():
        return (
            lambda cells, mask: serving.plan_remove_sensor(
                dataclasses.replace(fx.plan, cells=cells, cell_mask=mask),
                jnp.asarray(5, jnp.int32),
            ),
            (fx.plan.cells, fx.plan.cell_mask),
        )
    entries.append(simple_entry("serving.plan_remove", build_plan_remove))

    def build_matvec():
        anchors = jnp.asarray(
            np.linspace(-1, 1, 10)[:, None], fx.dtype
        )
        coef = jnp.full((10,), 0.1, fx.dtype)
        return (
            lambda xq, an, cf: kernel_matvec(xq, an, cf, gamma=1.0),
            (fx.xq, anchors, coef),
        )
    # The Pallas matvec computes in float32 by contract (serving fast
    # path); on an f64 problem the input casts are intentional.
    entries.append(simple_entry(
        "kernels.matvec", build_matvec,
        allow_dtypes=frozenset({"float32"}),
    ))

    def build_watchdog():
        return (
            lambda z, coef, z2, coef2: monitor._round_metrics(
                fx.prob, SNTrainState(z, coef), SNTrainState(z2, coef2)
            ),
            (fx.state.z, fx.state.coef, fx.state.z, fx.state.coef),
        )
    entries.append(simple_entry("monitor.watchdog_step", build_watchdog))

    return entries


def run(trace_dtype: str = "float32") -> list[Finding]:
    """Audit the full default registry at ``trace_dtype``."""
    return run_entries(
        default_entries(trace_dtype), trace_dtype=trace_dtype
    )
