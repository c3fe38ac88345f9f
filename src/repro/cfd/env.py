"""Gym-like cylinder AFC environment (the paper's DRL environment).

One ``env_step`` = one actuation period: the smoothed actuation amplitude
(eq. 11, beta = 0.4) is held while the solver advances ``steps_per_action``
dt's; the reward is eq. (12): r = C_D0 - <C_D> - omega_L |<C_L>|.

Everything is jit/vmap/shard_map-compatible.  The environment splits into a
**static** half (geometry fields, closed over as constants — shared by every
env in a batch) and a **traced** half (``ScenarioParams`` carried inside
``EnvState``: per-env Reynolds number, actuation mode, probe layout, C_D0),
so ``N_envs`` *heterogeneous* scenarios run as a single vmapped program on
the "data" mesh axis (the paper's multi-environment parallelism extended to
the scenario-diversity axis; see ``repro.cfd.scenarios``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cfd import poisson
from repro.cfd import probes as probes_mod
from repro.cfd import scenarios as scn_mod
from repro.cfd import solver
from repro.cfd.grid import GridConfig, build_geometry
from repro.cfd.scenarios import Scenario, ScenarioParams
from repro.testing import faults


@dataclass(frozen=True)
class EnvConfig:
    """Environment configuration.

    ``cd0`` is the uncontrolled mean drag entering reward eq. (12).  The
    paper's value on its OpenFOAM mesh is 3.205; our IB grid at moderate
    resolution gives ~3.5-3.7 (resolution-dependent).  ``cd0=None`` (the
    default) means "calibrate it from the uncontrolled warmup run"; any
    float — including 0.0 — is used as-is, no calibration.

    ``obs_dim`` is derived from ``probe_layout`` (see ``repro.cfd.probes``),
    not hardcoded; ``actuation`` selects synthetic jets vs. rotary control.
    """
    grid: GridConfig = GridConfig()
    steps_per_action: int = 50
    actions_per_episode: int = 100
    beta: float = 0.4             # action smoothing, eq. (11)
    reward_omega: float = 0.1     # lift penalty weight, eq. (12)
    cd0: Optional[float] = None   # None -> calibrate during warmup
    warmup_time: float = 30.0     # t.u. of uncontrolled flow before training
    probe_layout: str = "ring149"
    actuation: str = "jets"
    geometry: str = "cylinder"    # immersed-body set (repro.cfd.grid)
    guard: bool = True            # divergence sentinel + per-env quarantine
    guard_vel_limit: float = 50.0   # |u|,|v| ceiling (U_m is O(1))
    guard_div_limit: float = 1e3    # max |div(u,v)| ceiling post-projection

    @property
    def obs_dim(self) -> int:
        return probes_mod.layout_size(self.probe_layout)

    @property
    def act_dim(self) -> int:
        return self.scenario().act_dim

    @property
    def action_max(self) -> float:
        return self.grid.u_max    # |V_jet| <= U_m constraint

    def scenario(self, name: str = "__cfg__") -> Scenario:
        """The (anonymous) scenario this config describes."""
        return Scenario(name=name, re=self.grid.re, actuation=self.actuation,
                        probes=self.probe_layout, geometry=self.geometry,
                        cd0=self.cd0)

    @classmethod
    def for_scenario(cls, scn, **overrides) -> "EnvConfig":
        """EnvConfig bound to a registered scenario (or Scenario object)."""
        scn = scn if isinstance(scn, Scenario) else scn_mod.get_scenario(scn)
        grid = overrides.pop("grid", GridConfig())
        grid = dataclasses.replace(grid, re=scn.re)
        return cls(grid=grid, probe_layout=scn.probes,
                   actuation=scn.actuation, geometry=scn.geometry,
                   cd0=scn.cd0, **overrides)


class EnvState(NamedTuple):
    """The trailing ``reset_flow`` field defaults to None (absent): jax.tree
    treats None as an empty subtree, so 4-field states — and every program
    traced before the divergence sentinel existed — keep their structure.
    When present it carries the scenario's cached warmup flow so a diverged
    env can be quarantined (re-initialized) inside the vmapped program."""
    flow: solver.FlowState
    jet_vel: jnp.ndarray          # smoothed actuation amplitude — scalar, or
    #                               (A,) per-body surface speeds (multi-body)
    t: jnp.ndarray                # actuation counter
    scn: ScenarioParams           # traced per-env scenario parameters
    reset_flow: solver.FlowState = None   # warmup flow for quarantine resets


class EnvOutput(NamedTuple):
    obs: jnp.ndarray              # (obs_dim,) pressure probes (padded)
    reward: jnp.ndarray
    cd: jnp.ndarray               # mean C_D over the actuation period
    cl: jnp.ndarray
    valid: jnp.ndarray = None     # 1.0 healthy / 0.0 quarantined (sentinel)


class CylinderEnv:
    """Factory for pure env functions bound to a geometry.

    The geometry (masks, actuation target fields, inlet profile) is built
    once and closed over; ``env_step`` reads all per-scenario physics from
    ``state.scn``, so one CylinderEnv serves an arbitrary scenario mix.

    ``backend``/``mesh`` select the solver backend for the env steps
    training integrates.  ``backend="fused"`` runs each actuation interval
    through ``repro.kernels.actuation`` (fields and packed pressure planes
    carried across all ``steps_per_action`` dt's; VMEM-resident Pallas
    megakernel on TPU, one fused XLA scan elsewhere; odd-width or
    over-VMEM-budget grids fall back to the reference scan with a
    once-per-shape warning).  ``backend="halo"`` with a ("data", "model") mesh
    runs each env's pressure solve as explicit x-slabs over the "model"
    axis (the plan's n_ranks).  Warmup always runs the un-decomposed
    backend: its group batch is too small to tile the mesh "data" axis,
    and the two backends solve the same
    equations — the halo path's block-Jacobi boundary lag is a solver
    tolerance, not a different operator, so the developed flow and C_D0
    transfer."""

    def __init__(self, cfg: EnvConfig = EnvConfig(), *,
                 backend: Optional[str] = None, mesh=None):
        self.cfg = cfg
        self.backend = poisson.resolve_backend(backend)
        self.mesh = mesh
        if self.backend == "halo":
            from repro.cfd.decomp import validate_decomposition
            if mesh is None:
                raise ValueError("backend='halo' needs mesh= (e.g. "
                                 "launch.mesh.mesh_for_plan(plan))")
            validate_decomposition(mesh, cfg.grid.nx)
        self.geom = build_geometry(cfg.grid, cfg.geometry)
        self.geom_arrays = solver.geom_to_arrays(self.geom)
        self._reset_flow = None
        self._geom_cache = {cfg.geometry: (self.geom, self.geom_arrays)}
        self._bank = None        # stacked (G, ...) GeomArrays, built lazily
        self._group_cache = {}   # (re, act_mode, geometry) -> (FlowState, cd0)

    # -- uncontrolled warmup to a developed shedding state ------------------

    def warmup(self, verbose: bool = False) -> solver.FlowState:
        """Run (or fetch from the group cache) the uncontrolled warmup for
        this config's own (Re, actuation) group — the zero-amplitude flow
        still depends on the actuation mode because each mode's penalization
        band differs — and calibrate ``cd0`` from its tail when unset."""
        cfg = self.cfg
        group = (cfg.grid.re, cfg.scenario().act_mode, cfg.geometry)
        self._warmup_groups([group])
        flow, cd0 = self._group_cache[group]
        self._reset_flow = flow
        if self.cfg.cd0 is None:  # calibrate C_D0 on the uncontrolled flow
            self.cfg = dataclasses.replace(self.cfg, cd0=cd0)
        if verbose:
            n = max(1, int(round(cfg.warmup_time / cfg.grid.dt)))
            print(f"warmup {n} steps: CD0={self.cfg.cd0:.3f}")
        return solver.FlowState(*jax.tree.map(jnp.asarray, flow))

    def _run_steps(self, n, flow, jet_vel, re=None, act_mode=None,
                   geom_arrays=None):
        # warmup path: un-decomposed backend (see class docstring); the
        # fused interval path serves warmup too (same operator, one scan)
        backend = "reference" if self.backend == "halo" else self.backend
        ga = self.geom_arrays if geom_arrays is None else geom_arrays
        flow, outs = solver.step_interval(self.cfg.grid, ga,
                                          flow, jet_vel, n, re=re,
                                          act_mode=act_mode, backend=backend)
        return flow, (outs.cd, outs.cl)

    # -- multi-geometry support ---------------------------------------------

    def _geometry(self, name: str):
        """(Geometry, GeomArrays) for a named body set, built once."""
        if name not in self._geom_cache:
            geom = build_geometry(self.cfg.grid, name)
            self._geom_cache[name] = (geom, solver.geom_to_arrays(geom))
        return self._geom_cache[name]

    def _ensure_bank(self) -> None:
        """Stack every registered geometry's arrays into one (G, ...) bank.

        Per-body fields are zero-padded to ``grid.max_bodies()`` so all
        geometries share one shape; each env then gathers its own slab with
        ``scn.geom_id`` inside the vmapped program — mixed cylinder+pinball
        batches stay ONE XLA program."""
        if self._bank is not None:
            return
        from repro.cfd import grid as grid_mod
        bmax = grid_mod.max_bodies()

        def padded(ga):
            def pad(a):
                if a.shape[0] == bmax:
                    return a
                fill = jnp.zeros((bmax - a.shape[0],) + a.shape[1:], a.dtype)
                return jnp.concatenate([a, fill])
            return ga._replace(rotb_u=pad(ga.rotb_u), rotb_v=pad(ga.rotb_v),
                               own_u=pad(ga.own_u), own_v=pad(ga.own_v))

        per = [padded(self._geometry(n)[1])
               for n in grid_mod.geometry_names()]
        self._bank = jax.tree.map(lambda *xs: jnp.stack(xs), *per)

    def _env_geom(self, scn: ScenarioParams):
        """This env's geometry arrays: the closed-over static set, or a
        per-env gather from the bank when the batch mixes geometries."""
        if self._bank is None or scn.geom_id is None:
            return self.geom_arrays
        return jax.tree.map(lambda x: x[scn.geom_id], self._bank)

    # -- pure env API --------------------------------------------------------

    def reset(self) -> Tuple[EnvState, jnp.ndarray]:
        if self._reset_flow is None:
            self.warmup()
        flow = jax.tree.map(jnp.asarray, self._reset_flow)
        scn = self.cfg.scenario()
        params = scn_mod.scenario_params(scn, self.cfg.grid,
                                         cd0=self.cfg.cd0)
        jet0 = (jnp.float32(0.0) if scn.act_dim == 1
                else jnp.zeros(scn.act_dim, jnp.float32))
        flow0 = solver.FlowState(*flow)
        st = EnvState(flow=flow0, jet_vel=jet0,
                      t=jnp.int32(0), scn=params,
                      reset_flow=flow0 if self.cfg.guard else None)
        return st, self._observe(st)

    def reset_batch(self, scenarios: Sequence, n_envs: Optional[int] = None,
                    *, obs_dim: Optional[int] = None,
                    act_dim: Optional[int] = None,
                    ) -> Tuple[EnvState, jnp.ndarray]:
        """Mixed-scenario reset: an (N_envs, ...) batch with per-env physics.

        ``scenarios``: names and/or Scenario objects, assigned round-robin
        over ``n_envs`` (default: one env per scenario).  Warmup runs once
        per distinct *(Re, actuation, geometry)* triple, vmapped per
        geometry — the actuation mode matters even at zero amplitude because
        each mode's penalization band differs, so the developed flow and
        C_D0 must come from the same operator ``env_step`` will integrate.
        Per-scenario C_D0 is calibrated from each warmup tail unless the
        scenario pins one; results are cached, so repeated resets with the
        same scenario set re-run nothing.  Probe layouts are padded to a
        common ``obs_dim`` and action vectors to a common ``act_dim``
        (default: widest in the batch; ``act_dim == 1`` keeps the
        historical scalar-amplitude state).  A batch whose geometries stray
        from the config's builds the geometry bank so every env gathers its
        own body set inside one vmapped program.
        """
        cfg = self.cfg
        scns = scn_mod.assign_envs(scenarios, n_envs or len(scenarios))
        groups = sorted({(s.re, s.act_mode, s.geometry) for s in scns})
        self._warmup_groups(groups)
        if any(s.geometry != cfg.geometry for s in scns):
            self._ensure_bank()

        flows, cd0s = [], []
        for s in scns:
            flow, cd0 = self._group_cache[(s.re, s.act_mode, s.geometry)]
            flows.append(flow)
            cd0s.append(s.cd0 if s.cd0 is not None else cd0)
        flow_b = jax.tree.map(lambda *xs: jnp.stack(xs),
                              *[jax.tree.map(jnp.asarray, f) for f in flows])
        params_b = scn_mod.batch_params(scns, cfg.grid, obs_dim=obs_dim,
                                        act_dim=act_dim, cd0s=cd0s)
        a_dim = (scn_mod.common_act_dim(scns) if act_dim is None else act_dim)
        jet0 = (jnp.zeros(len(scns), jnp.float32) if a_dim == 1
                else jnp.zeros((len(scns), a_dim), jnp.float32))
        flow0_b = solver.FlowState(*flow_b)
        st_b = EnvState(flow=flow0_b,
                        jet_vel=jet0,
                        t=jnp.zeros(len(scns), jnp.int32), scn=params_b,
                        reset_flow=flow0_b if cfg.guard else None)
        obs_b = jax.vmap(self._observe)(st_b)
        return st_b, obs_b

    def _warmup_groups(self, groups) -> None:
        """Warm up every uncached (re, act_mode, geometry) group, one vmapped
        run per geometry (each geometry's masks are distinct closure
        constants, so they cannot share a trace without banking — and warmup
        runs once per cache lifetime, where compile time dominates anyway)."""
        cfg = self.cfg
        todo = [g for g in groups if g not in self._group_cache]
        if not todo:
            return
        by_geom: dict = {}
        for g in todo:
            by_geom.setdefault(g[2], []).append(g)
        n = max(1, int(round(cfg.warmup_time / cfg.grid.dt)))
        tail = max(1, n // 4)
        for gname, gtodo in sorted(by_geom.items()):
            geom, ga = self._geometry(gname)
            flow0 = solver.init_state(cfg.grid, geom)
            run = jax.jit(jax.vmap(
                lambda re, m: self._run_steps(n, flow0, jnp.float32(0.0),
                                              re=re, act_mode=m,
                                              geom_arrays=ga)))
            flows, (cds, _) = run(
                jnp.asarray([g[0] for g in gtodo], jnp.float32),
                jnp.asarray([g[1] for g in gtodo], jnp.float32))
            cd0s = np.asarray(jnp.mean(cds[:, -tail:], axis=1))
            for i, g in enumerate(gtodo):
                flow = jax.tree.map(lambda a, i=i: np.asarray(a[i]), flows)
                self._group_cache[g] = (solver.FlowState(*flow),
                                        float(cd0s[i]))

    @jax.named_scope("probes")
    def _observe(self, st: EnvState) -> jnp.ndarray:
        return probes_mod.sample_pressure(st.scn.probe_ij, st.flow.p,
                                          st.scn.probe_mask)

    def obs_aux(self, st: EnvState) -> dict:
        """Observation side-channel for set-structured policies: normalized
        probe coordinates in [-1, 1]^2 plus the live-slot mask.  Constant
        over an episode (the layout rides in ``st.scn``), so rollouts fetch
        it once per reset, not per step."""
        g = self.cfg.grid
        ij = jnp.asarray(st.scn.probe_ij, jnp.float32)
        y = ij[..., 0] / max(g.ny - 1, 1) * 2.0 - 1.0
        x = ij[..., 1] / max(g.nx - 1, 1) * 2.0 - 1.0
        return {"xy": jnp.stack([x, y], axis=-1),
                "mask": jnp.asarray(st.scn.probe_mask, jnp.float32)}

    def env_step(self, st: EnvState, action) -> Tuple[EnvState, EnvOutput]:
        """One actuation period.  action: in [-1, 1], scalar (jet velocity
        or uniform rotary surface speed) or (A,) per-body surface speeds —
        the shape follows ``st.jet_vel``; padded slots beyond a scenario's
        own act_dim are zeroed by ``st.scn.act_mask``."""
        cfg = self.cfg
        a = jnp.clip(action, -1.0, 1.0) * cfg.action_max
        per_body = jnp.ndim(st.jet_vel) > 0          # static (trace-time)
        if per_body and st.scn.act_mask is not None:
            a = a * st.scn.act_mask
        jet = st.jet_vel + cfg.beta * (a - st.jet_vel)        # eq. (11)
        jet = jnp.clip(jet, -cfg.action_max, cfg.action_max)

        flow_in = st.flow
        fz = faults.active("nan_env")
        if fz is not None:       # trace-time gate: absent in production traces
            # the env's index in its vmapped batch: on a data-parallel mesh,
            # each device's slice of the batch (RolloutEngine)
            idx = jax.lax.axis_index("env")
            hit = ((idx == int(fz.get("env", 0)))
                   & (st.t == int(fz.get("step", 0))))
            poison = jnp.where(hit, jnp.float32(jnp.nan), jnp.float32(0.0))
            flow_in = flow_in._replace(u=flow_in.u + poison)

        # the whole actuation interval runs as one unit: backend="fused"
        # carries the fields (and packed pressure planes) across every dt
        # with no per-dt round-trips; other backends scan solver.step
        flow, outs = solver.step_interval(cfg.grid, self._env_geom(st.scn),
                                          flow_in, jet,
                                          cfg.steps_per_action,
                                          re=st.scn.re,
                                          act_mode=st.scn.act_mode,
                                          backend=self.backend,
                                          mesh=self.mesh)
        with jax.named_scope("reward"):
            if outs.cd.ndim > 1:
                # per-body (n_steps, B) coefficients: the reward drag term is
                # the total, but lift is penalized per body — opposite-signed
                # body lifts must not cancel into a spurious zero penalty
                cd_b = jnp.mean(outs.cd, axis=0)
                cl_b = jnp.mean(outs.cl, axis=0)
                cd = jnp.sum(cd_b)
                cl = jnp.sum(cl_b)
                cl_pen = jnp.sum(jnp.abs(cl_b))
            else:
                cd = jnp.mean(outs.cd)
                cl = jnp.mean(outs.cl)
                cl_pen = jnp.abs(cl)
            reward = st.scn.cd0 - cd - cfg.reward_omega * cl_pen  # eq. (12)
        if st.reset_flow is None:     # sentinel off: the pre-guard program
            st2 = EnvState(flow=flow, jet_vel=jet, t=st.t + 1, scn=st.scn)
            return st2, EnvOutput(obs=self._observe(st2), reward=reward,
                                  cd=cd, cl=cl)

        # -- divergence sentinel: quarantine a blown-up env in-place --------
        # ``jnp.where(True, a, b)`` passes ``a`` through exactly, so an
        # all-healthy batch stays bitwise-identical to the unguarded program.
        ok = self._healthy(flow, reward)
        sel = lambda h, q: jnp.where(ok, h, q)                  # noqa: E731
        st2 = EnvState(flow=jax.tree.map(sel, flow, st.reset_flow),
                       jet_vel=sel(jet, jnp.zeros_like(jet)),
                       t=st.t + 1, scn=st.scn, reset_flow=st.reset_flow)
        zero = jnp.float32(0.0)
        return st2, EnvOutput(obs=self._observe(st2),
                              reward=sel(reward, zero),
                              cd=sel(cd, zero), cl=sel(cl, zero),
                              valid=ok.astype(jnp.float32))

    def _healthy(self, flow: solver.FlowState, reward) -> jnp.ndarray:
        """Traced per-env health check: finite fields + physical ceilings.

        NaN/Inf fail the ``<`` comparisons, so a single fused reduction per
        field covers both finiteness and magnitude.  The ceilings are far
        above any physical value (U_m is O(1)): they flag a diverging solve,
        not an unusual flow."""
        cfg = self.cfg
        vmax = jnp.maximum(jnp.max(jnp.abs(flow.u)), jnp.max(jnp.abs(flow.v)))
        divmax = jnp.max(jnp.abs(solver.divergence(flow.u, flow.v, cfg.grid)))
        return ((vmax < cfg.guard_vel_limit)
                & (divmax < cfg.guard_div_limit)
                & jnp.isfinite(jnp.max(jnp.abs(flow.p)))
                & jnp.isfinite(reward))
