"""Fitness evaluation — decode + BW-allocate + objective, over populations.

The evaluator is built once per (Job Analysis Table, system BW, objective)
and then called inside the optimization loop; a single jitted vmapped scan
evaluates the entire population (~1 ms per 100-individual epoch on CPU,
vs. the paper's 0.25 s/epoch on a desktop CPU).

Two call forms exist:

  - ``FitnessFn(...)`` — the object-style evaluator used by every mapper
    (Table IV).  Its ``__call__`` is pure JAX, so it can be traced inside
    ``jax.lax.scan`` / ``jax.vmap`` (the device-resident MAGMA engine calls
    it from inside its generation scan).
  - ``evaluate_params(params, accel, prio, ...)`` — a functional form whose
    scenario data (``FitnessParams``: lat/bw tables, system BW, FLOPs,
    objective code) is *traced* rather than closed over.  Stacking several
    ``FitnessParams`` along a leading axis and ``jax.vmap``-ing this
    function is how ``magma_search_batch`` runs whole scenario grids
    (Fig. 8/9/13/17) as one XLA program.

Objectives (Section IV-C) are registry-backed: :func:`register_objective`
adds a named column function (mirroring ``strategies.registry``), and an
:class:`ObjectiveSpec` names one or several registered objectives.  A
scalar spec evaluates through :func:`evaluate_params` exactly as the bare
name always did (bit-identical traces — the memo's exact-hit guarantee
depends on this); a multi-column spec evaluates through
:func:`evaluate_objectives` to a ``(P, M)`` objective matrix, which is
what makes every registered ``SearchStrategy`` multi-objective for free
(``repro.core.strategies.nsga2``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bw_allocator import simulate_population, throughput
from repro.core.job_analyzer import JobAnalysisTable


# ---------------------------------------------------------------------------
# objective registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ObjectiveInfo:
    """Registry entry: one named objective column.

    ``fn(params, ms, en) -> (P,)`` maps the traced scenario data plus the
    per-candidate makespans ``ms`` (None when ``needs_makespan`` is False)
    and total energies ``en`` (None when ``needs_energy`` is False) to a
    higher-is-better fitness column.  ``code`` is the stable i32 the
    dynamic (per-scenario traced) select dispatches on; codes are assigned
    in registration order and never reassigned.
    """
    name: str
    code: int
    fn: Callable[..., jnp.ndarray]
    needs_energy: bool = False
    needs_makespan: bool = True
    description: str = ""


_OBJECTIVES: Dict[str, ObjectiveInfo] = {}

# live back-compat view (name -> code); kept in sync by register_objective
OBJECTIVE_CODES: Dict[str, int] = {}


def register_objective(name: str, fn: Callable[..., jnp.ndarray], *,
                       needs_energy: bool = False,
                       needs_makespan: bool = True,
                       description: str = "",
                       overwrite: bool = False) -> ObjectiveInfo:
    """Register a named objective column (mirrors ``strategies.register``).

    ``fn(params, ms, en)`` must be pure JAX over a ``FitnessParams`` plus
    the shared per-candidate makespans/energies, returning a ``(P,)``
    higher-is-better column.  Re-registering an existing name requires
    ``overwrite=True`` and keeps its code (memo fingerprints embed codes
    through ``objective_code``; they must never be reassigned).
    """
    if name in _OBJECTIVES:
        if not overwrite:
            raise ValueError(f"objective {name!r} is already registered")
        code = _OBJECTIVES[name].code
    else:
        code = len(_OBJECTIVES)
    info = ObjectiveInfo(name=name, code=code, fn=fn,
                         needs_energy=bool(needs_energy),
                         needs_makespan=bool(needs_makespan),
                         description=description)
    _OBJECTIVES[name] = info
    OBJECTIVE_CODES[name] = code
    return info


def objective_info(name: str) -> ObjectiveInfo:
    """Metadata for a registered objective; unknown names raise a
    ``ValueError`` listing what is registered."""
    if name not in _OBJECTIVES:
        raise ValueError(
            f"unknown objective {name!r}; registered objectives: "
            f"{', '.join(available_objectives())}")
    return _OBJECTIVES[name]


def available_objectives() -> Tuple[str, ...]:
    """Registered objective names in code (registration) order."""
    return tuple(sorted(_OBJECTIVES, key=lambda n: _OBJECTIVES[n].code))


def registered_objectives() -> Tuple[ObjectiveInfo, ...]:
    """All registry entries in code order (the dynamic-select order)."""
    return tuple(sorted(_OBJECTIVES.values(), key=lambda i: i.code))


# the paper's four (Section IV-C), at their historical codes 0..3 — the
# exact expressions the pre-registry static branches computed, so scalar
# evaluation stays bit-identical
register_objective(
    "throughput", lambda params, ms, en: throughput(params.flops, ms),
    description="group FLOPs / makespan (the paper's default)")
register_objective(
    "latency", lambda params, ms, en: -ms,
    description="negated makespan")
register_objective(
    "energy", lambda params, ms, en: -en,
    needs_energy=True, needs_makespan=False,
    description="negated total assignment energy (order-free)")
register_objective(
    "edp", lambda params, ms, en: -en * ms,
    needs_energy=True,
    description="negated energy-delay product")


# ---------------------------------------------------------------------------
# ObjectiveSpec — scalar names generalized to vector-valued objectives
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ObjectiveSpec:
    """A frozen, registry-backed objective: one or more named columns.

    Hashable (usable as a jit static argument / executable-cache key).
    A 1-column spec is the degenerate scalar case and evaluates
    bit-identically to the bare objective name — including its memo
    ``token``, so pre-spec records still exact-hit.
    """
    names: Tuple[str, ...]

    def __post_init__(self):
        names = tuple(self.names)
        object.__setattr__(self, "names", names)
        if not names:
            raise ValueError("ObjectiveSpec needs at least one objective")
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate objectives in {names}")
        for n in names:
            objective_info(n)        # raises listing what is registered

    @property
    def num_objectives(self) -> int:
        return len(self.names)

    @property
    def is_scalar(self) -> bool:
        return len(self.names) == 1

    @property
    def token(self) -> str:
        """Canonical string identity for fingerprints and compat keys.

        A scalar spec's token IS the bare name, byte-identical to the
        pre-spec fingerprint format; multi-column specs get a distinct
        ``pareto:`` form."""
        if self.is_scalar:
            return self.names[0]
        return "pareto:" + "+".join(self.names)

    @property
    def needs_energy(self) -> bool:
        return any(objective_info(n).needs_energy for n in self.names)

    @property
    def codes(self) -> Tuple[int, ...]:
        return tuple(objective_info(n).code for n in self.names)

    def infos(self) -> Tuple[ObjectiveInfo, ...]:
        return tuple(objective_info(n) for n in self.names)


ObjectiveLike = Union[str, Sequence[str], ObjectiveSpec, None]


def as_objective_spec(objective: ObjectiveLike) -> Optional[ObjectiveSpec]:
    """Coerce a bare name / name sequence / spec to an ``ObjectiveSpec``
    (``None`` stays ``None`` — the dynamic per-scenario traced select)."""
    if objective is None or isinstance(objective, ObjectiveSpec):
        return objective
    if isinstance(objective, str):
        return ObjectiveSpec((objective,))
    return ObjectiveSpec(tuple(objective))


def objective_token(objective: ObjectiveLike) -> Optional[str]:
    """The canonical string the memo/compat layers key on: scalar specs
    and bare names collapse to the same token (``None`` passes through)."""
    spec = as_objective_spec(objective)
    return None if spec is None else spec.token


class FitnessParams(NamedTuple):
    """Traced scenario data — everything the fitness needs besides genomes.

    All leaves are arrays, so a batch of scenarios with the same (G, A)
    shape stacks along a leading axis and vmaps.
    """
    lat: jnp.ndarray             # (G, A) f32 no-stall latencies
    bw: jnp.ndarray              # (G, A) f32 required bandwidths
    bw_sys: jnp.ndarray          # ()     f32 system bandwidth
    flops: jnp.ndarray           # ()     f32 total group FLOPs
    energy: jnp.ndarray          # (G, A) f32 (zeros when table has none)
    objective_code: jnp.ndarray  # () i32 registry code — (M,) for a
    #                              multi-column ObjectiveSpec


def population_energies(energy: jnp.ndarray, accel: jnp.ndarray) -> jnp.ndarray:
    """(P,) total group energy (J) of each assignment — order-free
    (Section IV-C alternative objectives)."""
    return jax.vmap(
        lambda a: jnp.take_along_axis(energy, a[:, None], axis=1).sum())(accel)


def _population_makespans(params: FitnessParams, accel, prio, *,
                          num_accels: int, use_kernel) -> jnp.ndarray:
    """``use_kernel``: False runs the jnp event scan, True the Pallas
    kernel compiled with Mosaic (TPU only), ``kernels.ops.INTERPRET`` the
    same kernel in the Pallas interpreter."""
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.population_makespan(
            accel, prio, params.lat, params.bw, params.bw_sys, num_accels,
            interpret=use_kernel == kops.INTERPRET)
    return simulate_population(accel, prio, params.lat, params.bw,
                               params.bw_sys, num_accels)


def evaluate_params(params: FitnessParams, accel: jnp.ndarray,
                    prio: jnp.ndarray, *, num_accels: int,
                    use_kernel: bool = False,
                    objective: ObjectiveLike = None) -> jnp.ndarray:
    """(P,) fitness values — higher is better for every objective.

    ``objective`` may be a static registered name (or a 1-column
    ``ObjectiveSpec``), in which case only that column's branch is
    computed, or ``None``, in which case the column is selected
    element-wise by ``params.objective_code`` — the form
    ``magma_search_batch`` uses so scenarios with different objectives can
    share one compiled program.  Multi-column specs go through
    :func:`evaluate_objectives` instead.
    """
    spec = as_objective_spec(objective)
    if spec is not None and not spec.is_scalar:
        raise ValueError(
            f"evaluate_params is scalar; objective {spec.token!r} has "
            f"{spec.num_objectives} columns — use evaluate_objectives")
    if spec is not None:
        info = objective_info(spec.names[0])
        ms = (_population_makespans(params, accel, prio,
                                    num_accels=num_accels,
                                    use_kernel=use_kernel)
              if info.needs_makespan else None)
        en = (population_energies(params.energy, accel)
              if info.needs_energy else None)
        return info.fn(params, ms, en)

    # dynamic objective: branch-free select on the traced code, over every
    # registered column in code order
    ms = _population_makespans(params, accel, prio, num_accels=num_accels,
                               use_kernel=use_kernel)
    en = population_energies(params.energy, accel)
    infos = registered_objectives()
    vals = [info.fn(params, ms, en) for info in infos]
    code = params.objective_code
    return jnp.select([code == info.code for info in infos[:-1]],
                      vals[:-1], vals[-1])


def evaluate_objectives(params: FitnessParams, accel: jnp.ndarray,
                        prio: jnp.ndarray, *, num_accels: int,
                        use_kernel: bool = False,
                        objective: ObjectiveLike = None) -> jnp.ndarray:
    """(P, M) objective matrix — column ``j`` is ``objective.names[j]``,
    higher is better, and bit-identical to the scalar
    :func:`evaluate_params` of that name alone (the shared makespan/energy
    intermediates are computed by exactly the same expressions).

    ``objective`` must coerce to a static ``ObjectiveSpec`` (the dynamic
    ``None`` form has no static column count to shape the matrix with).
    """
    spec = as_objective_spec(objective)
    if spec is None:
        raise ValueError(
            "evaluate_objectives needs a static ObjectiveSpec (or name "
            "sequence); the dynamic objective=None form is scalar-only")
    infos = spec.infos()
    ms = (_population_makespans(params, accel, prio, num_accels=num_accels,
                                use_kernel=use_kernel)
          if any(i.needs_makespan for i in infos) else None)
    en = (population_energies(params.energy, accel)
          if any(i.needs_energy for i in infos) else None)
    return jnp.stack([info.fn(params, ms, en) for info in infos], axis=-1)


def stack_fitness_params(fns: Sequence["FitnessFn"]) -> FitnessParams:
    """Stack the params of several same-shape FitnessFns along axis 0."""
    assert len(fns) > 0, "need at least one scenario"
    G, A = fns[0].params.lat.shape
    for f in fns[1:]:
        if f.params.lat.shape != (G, A):
            raise ValueError(
                f"scenario tables must share (G, A)={G, A}; "
                f"got {f.params.lat.shape}")
        if f.num_accels != fns[0].num_accels:
            raise ValueError("scenarios must share num_accels")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[f.params for f in fns])


class ProblemSpec(NamedTuple):
    """A normalized scenario batch: the stacked tables plus the statics a
    compiled row executable is specialized on.

    A NamedTuple on purpose — it *iterates and unpacks exactly like* the
    positional ``(params, num_accels, use_kernel, objective)`` 4-tuple
    ``normalize_scenarios`` used to return (the deprecation shim), while
    sweep, stream, and memo address the fields by name.  ``objective`` is
    the shared static ``ObjectiveSpec`` when every scenario agrees (so
    dead branches compile away), else ``None`` (per-scenario traced
    select).
    """
    params: FitnessParams
    num_accels: int
    use_kernel: bool
    objective: Optional[ObjectiveSpec]


def normalize_scenarios(scenarios, num_accels: Optional[int] = None,
                        use_kernel: bool = False) -> ProblemSpec:
    """Validate a scenario grid into a :class:`ProblemSpec`.

    ``scenarios`` is either an already-stacked ``FitnessParams`` (leading
    scenario axis; ``num_accels`` required) or a sequence of same-shape
    ``FitnessFn``s, which are stacked here.
    """
    if isinstance(scenarios, FitnessParams):
        if num_accels is None:
            raise ValueError("num_accels is required with raw FitnessParams")
        return ProblemSpec(scenarios, num_accels, use_kernel, None)
    fns = list(scenarios)
    # resolve the shared objective BEFORE stacking: a mixed multi/scalar
    # batch must fail with the objective diagnosis, not a shape error from
    # stacking ()-vs-(M,) objective_code leaves
    specs = {f.objective_spec for f in fns}
    if len(specs) == 1:
        objective = specs.pop()
    else:
        if any(not s.is_scalar for s in specs):
            raise ValueError(
                "a scenario batch with mixed objectives falls back to the "
                "dynamic per-scenario select, which is scalar-only; "
                "multi-column ObjectiveSpec scenarios must all share one "
                f"spec (got {sorted(s.token for s in specs)})")
        objective = None
    params = stack_fitness_params(fns)
    num_accels = fns[0].num_accels
    kernels = {f.use_kernel for f in fns}
    if len(kernels) > 1:
        raise ValueError(
            "scenarios must agree on use_kernel: the kernel and jnp "
            "simulators only match to ~1e-4, so a mixed batch cannot "
            "keep the bit-for-bit standalone guarantee")
    use_kernel = use_kernel or kernels.pop()
    return ProblemSpec(params, num_accels, use_kernel, objective)


def host_params(table: JobAnalysisTable, bw_sys: float,
                objective: ObjectiveLike = "throughput") -> FitnessParams:
    """A scenario's :class:`FitnessParams` as host numpy leaves: the exact
    values :class:`FitnessFn` places on the device, built without touching
    a device (a fleet parent ships these to the workers that own the
    chips)."""
    spec = as_objective_spec(objective)
    lat = np.asarray(table.lat, dtype=np.float32)
    energy = getattr(table, "energy", None)
    if spec.needs_energy and energy is None:
        raise ValueError(
            f"objective {spec.token!r} needs an energy column, "
            "but the job analysis table has none")
    # scalar specs keep the () i32 code (bit-identical pytree to the
    # pre-spec FitnessParams); multi-column specs carry an (M,) vector
    codes = spec.codes
    return FitnessParams(
        lat=lat,
        bw=np.asarray(table.bw, dtype=np.float32),
        bw_sys=np.float32(bw_sys),
        flops=np.float32(float(table.total_flops)),
        energy=(np.asarray(energy, dtype=np.float32) if energy is not None
                else np.zeros_like(lat)),
        objective_code=(np.int32(codes[0]) if spec.is_scalar
                        else np.asarray(codes, dtype=np.int32)),
    )


@dataclasses.dataclass
class FitnessFn:
    table: JobAnalysisTable
    bw_sys: float
    # a registered name ('throughput' | 'latency' | 'energy' | 'edp' | any
    # register_objective'd name), a sequence of names, or an ObjectiveSpec
    objective: ObjectiveLike = "throughput"
    # route through the Pallas makespan kernel: True compiles it with
    # Mosaic (TPU only), kernels.ops.INTERPRET runs it in the interpreter
    use_kernel: Union[bool, str] = False

    def __post_init__(self):
        self.bw_sys = float(self.bw_sys)
        spec = as_objective_spec(self.objective)
        if spec is None:
            raise ValueError("FitnessFn needs a concrete objective "
                             "(name, name sequence, or ObjectiveSpec)")
        self.objective_spec = spec
        self._A = int(self.table.num_accels)
        self.params = jax.tree.map(
            jnp.asarray, host_params(self.table, self.bw_sys, spec))
        self._energy = (self.params.energy
                        if getattr(self.table, "energy", None) is not None
                        else None)

    def energies(self, accel: jnp.ndarray) -> jnp.ndarray:
        """(P,) total group energy (J) of each assignment — order-free
        (Section IV-C alternative objectives)."""
        assert self._energy is not None, "table has no energy column"
        return population_energies(self._energy, accel)

    def __call__(self, accel: jnp.ndarray, prio: jnp.ndarray) -> jnp.ndarray:
        """(P,) fitness values — higher is better for every objective.

        Pure JAX: traceable from inside jit / scan / vmap.  Scalar specs
        only; a multi-column spec evaluates via :meth:`objectives`."""
        return evaluate_params(self.params, accel, prio,
                               num_accels=self._A, use_kernel=self.use_kernel,
                               objective=self.objective_spec)

    def objectives(self, accel: jnp.ndarray, prio: jnp.ndarray) -> jnp.ndarray:
        """(P, M) objective matrix for this scenario's spec (M=1 for a
        scalar spec) — pure JAX, column ``j`` bit-identical to the scalar
        evaluation of ``objective_spec.names[j]``."""
        return evaluate_objectives(self.params, accel, prio,
                                   num_accels=self._A,
                                   use_kernel=self.use_kernel,
                                   objective=self.objective_spec)

    @property
    def num_objectives(self) -> int:
        return self.objective_spec.num_objectives

    @property
    def num_accels(self) -> int:
        return self._A

    @property
    def group_size(self) -> int:
        return self.table.group_size
