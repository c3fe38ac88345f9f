"""The reference run of a cell's first episodes.

``view`` replays what a run recorded (the actions it took, the batches its
learner consumed, its state after each update) through the plain reference
and returns what the reference makes of the same inputs; ``control`` builds
a whole record from the reference computed in bfloat16, the precision below
the float32 the configuration states.  Both start from the seed alone: the
reference draws its own initial weights, builds its own geometry and probe
tables, and runs its own uncontrolled warm-up.  The recorded state is only
ever loaded as the input of one reference step, never taken as a result."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import check
from bench.reference import flow, learner
from bench.reference import geometry as geo_mod
from bench.reference import policy as pol


def _grid(cfg):
    return geo_mod.Grid(res=cfg["res"], dt=cfg["dt"], re=100.0,
                        poisson_iters=cfg["poisson_iters"])


class Setup:
    """Per-env physics, geometry and the developed start flow of a cell."""

    def __init__(self, cfg: dict, traffic: dict, n_envs: int, dtype):
        self.dtype = dtype
        g = self.grid = _grid(cfg)
        scns = cfg["scenarios"]
        self.per_env = [scns[i % len(scns)] for i in range(n_envs)]
        names = sorted({s["geometry"] for s in scns})
        self.geos = flow.geometry_stack(g, names, dtype)
        self.obs_dim = max(len(geo_mod.PROBES[s["probes"]]()) for s in scns)
        bodies = {s["name"]: (len(geo_mod.BODIES[s["geometry"]])
                              if s["actuation"] == "rotary" else 1)
                  for s in scns}
        self.act_dim = max(bodies.values())
        self.vector = len(scns) > 1 or self.act_dim > 1
        groups = sorted({(s["re"], s["actuation"], s["geometry"])
                         for s in scns})
        n_warm = max(1, int(round(cfg["warmup_time"] / cfg["dt"])))
        warm = {}
        for re, act, gname in groups:
            geo = jax.tree.map(lambda x, i=names.index(gname): x[i], self.geos)
            run = jax.jit(functools.partial(flow.warmup, g, n_steps=n_warm,
                                            dtype=dtype))
            warm[(re, act, gname)] = run(
                geo, jnp.asarray(re, dtype),
                jnp.asarray(1.0 if act == "rotary" else 0.0, dtype))
        rows = []
        for s in self.per_env:
            ij = geo_mod.probe_ij(g, s["probes"])
            pad = self.obs_dim - len(ij)
            rows.append(dict(
                re=np.float32(s["re"]),
                mode=np.float32(1.0 if s["actuation"] == "rotary" else 0.0),
                cd0=np.asarray(warm[(s["re"], s["actuation"],
                                     s["geometry"])][1], np.float32),
                probe_ij=np.concatenate([ij, np.zeros((pad, 2))]),
                probe_mask=np.concatenate([np.ones(len(ij)), np.zeros(pad)]),
                geom=np.int32(names.index(s["geometry"])),
                act_mask=(np.arange(self.act_dim)
                          < bodies[s["name"]]).astype(np.float32)))
        stack = lambda k: np.stack([r[k] for r in rows])       # noqa: E731
        self.phys = flow.Physics(
            re=jnp.asarray(stack("re"), dtype),
            mode=jnp.asarray(stack("mode"), dtype),
            cd0=jnp.asarray(stack("cd0"), dtype),
            probe_ij=jnp.asarray(stack("probe_ij"), jnp.float32),
            probe_mask=jnp.asarray(stack("probe_mask"), dtype),
            geom=jnp.asarray(stack("geom")),
            act_mask=jnp.asarray(stack("act_mask"), dtype))
        self.start = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[warm[(s["re"], s["actuation"], s["geometry"])][0]
              for s in self.per_env])
        self.jet0 = (jnp.zeros((n_envs, self.act_dim), dtype) if self.vector
                     else jnp.zeros((n_envs,), dtype))
        self.obs0 = jax.vmap(flow.probes)(self.start[2], self.phys.probe_ij,
                                          self.phys.probe_mask)
        # policy side channel: probe coordinates in [-1, 1] and live slots
        ij = np.asarray(self.phys.probe_ij)
        xy = np.stack([ij[..., 1] / max(g.nx - 1, 1) * 2 - 1,
                       ij[..., 0] / max(g.ny - 1, 1) * 2 - 1], axis=-1)
        self.xy = jnp.asarray(xy, dtype)
        self.mask = self.phys.probe_mask
        self._episode = jax.jit(functools.partial(
            flow.episode, g, n_steps=traffic["steps_per_action"]))

    def episode(self, actions):
        """Replay every env under ``actions`` (N, T, A) from the start flow."""
        out = self._episode(self.geos, self.phys, self.start, self.jet0,
                            jnp.asarray(actions, self.dtype))
        obs = jnp.concatenate([self.obs0[:, None], out["obs"][:, :-1]], 1)
        return {"obs": obs, "last_obs": out["obs"][:, -1],
                "reward": out["reward"], "cd": out["cd"], "cl": out["cl"],
                "valid": out["valid"]}


@functools.partial(jax.jit, static_argnames=("gamma", "lam"))
def _postprocess(params, obs, last_obs, reward, valid, xy, mask, gamma, lam):
    """Values of every step and the bootstrap, then GAE per env."""
    n, t = obs.shape[:2]
    rows = lambda x: jnp.repeat(x, t, axis=0)                  # noqa: E731
    feats = pol.features(params, obs.reshape(n * t, -1), rows(xy), rows(mask))
    values = pol.value(params, feats).reshape(n, t)
    last_v = pol.value(params, pol.features(params, last_obs, xy, mask))
    return jax.vmap(lambda r, v, lv, m: learner.gae(r, v, lv, m, gamma, lam))(
        reward, values, last_v, valid)


@jax.jit
def _logp(params, obs, act, xy, mask):
    n, t = obs.shape[:2]
    rows = lambda x: jnp.repeat(x, t, axis=0)                  # noqa: E731
    feats = pol.features(params, obs.reshape(n * t, -1), rows(xy), rows(mask))
    mean, log_std = pol.mean_std(params, feats)
    return pol.log_prob(act.reshape(n * t, -1), mean, log_std).reshape(n, t)


class Learner:
    """The reference policy and optimizer, stepped episode by episode."""

    def __init__(self, cfg, traffic, setup: Setup, seed: int):
        self.ppo = traffic["ppo"]
        self.setup = setup
        dt = setup.dtype
        key, kp = jax.random.split(jax.random.PRNGKey(seed))
        self.key = key
        self.params = jax.tree.map(
            lambda x: x.astype(dt),
            pol.init(cfg["policy"], setup.obs_dim, setup.act_dim, kp))
        self.opt = learner.adam_init(self.params)
        self.step = jnp.int32(0)

    def next_keys(self):
        self.key, kr, ku = jax.random.split(self.key, 3)
        return kr, ku

    def _cast(self, x):
        return jnp.asarray(x, self.setup.dtype)

    def logp(self, obs, act):
        s = self.setup
        return _logp(self.params, self._cast(obs), self._cast(act), s.xy,
                     s.mask)

    def advantages(self, traj):
        s = self.setup
        return _postprocess(self.params, self._cast(traj["obs"]),
                            self._cast(traj["last_obs"]),
                            self._cast(traj["reward"]),
                            self._cast(traj["valid"]), s.xy, s.mask,
                            gamma=self.ppo["gamma"], lam=self.ppo["lam"])

    def update(self, batch, ku):
        s = self.setup
        n, t = s.phys.re.shape[0], batch["adv"].shape[0] // s.phys.re.shape[0]
        rows = {k: self._cast(batch[k]) for k in
                ("obs", "act", "logp_old", "adv", "ret", "valid")}
        rows["xy"] = jnp.repeat(s.xy, t, axis=0)
        rows["mask"] = jnp.repeat(s.mask, t, axis=0)
        assert rows["obs"].shape[0] == n * t
        self.params, self.opt, self.step, metrics = learner.ppo_update(
            self.params, self.opt, rows, ku, self.step,
            ppo_items=tuple(sorted(self.ppo.items())))
        return {k: float(v) for k, v in metrics.items()}

    def state(self) -> dict:
        """The weights, Adam's moments (float32 host leaves) and the step."""
        return {"params": check.leaves(self.params),
                "m": check.leaves(self.opt["m"]),
                "v": check.leaves(self.opt["v"]), "step": int(self.step)}

    def load(self, state: dict):
        """Take a recorded state (``state()``'s form) as this learner's own;
        it has to hold every leaf of the reference's tree."""
        def tree(flat):
            paths, treedef = jax.tree_util.tree_flatten_with_path(self.params)
            return jax.tree_util.tree_unflatten(treedef, [
                self._cast(flat[jax.tree_util.keystr(k)]) for k, _ in paths])
        self.params = tree(state["params"])
        self.opt = {"m": tree(state["m"]), "v": tree(state["v"])}
        self.step = jnp.int32(state["step"])


def view(record: dict, cfg: dict, traffic: dict, seed: int,
         dtype=jnp.float32, setup: Setup = None) -> list:
    """The reference's reading of each recorded episode.

    Read under the program's own state at that step (teacher-forced): the
    log-probabilities of the recorded actions and the advantages and returns
    of the recorded steps, under the weights that collected the episode
    (the reference's initial weights for the first, else the run's weights
    after the update before it); and the metrics of one PPO update on the
    recorded batch from the run's state before that update.  Read by the
    reference's own learner, run from the seed through the compared updates
    on the recorded batches (free-running): its state after each of them.
    For the first ``check.episodes`` also the reference's own trajectory
    under the recorded actions."""
    eps = record["episodes"]
    n_env = traffic["check"]["episodes"]
    if setup is None:
        setup = Setup(cfg, traffic, eps[0]["traj"]["act"].shape[0], dtype)
    free = Learner(cfg, traffic, setup, seed)
    forced = Learner(cfg, traffic, setup, seed)
    out = []
    p0 = check.leaves(free.params)
    for k, ep in enumerate(eps):
        _, ku = free.next_keys()
        if k:
            forced.load(eps[k - 1])
        traj = ep["traj"]
        got = {"logp": np.asarray(forced.logp(traj["obs"], traj["act"]),
                                  np.float32)}
        adv, ret = forced.advantages(traj)
        got["adv"] = np.asarray(adv, np.float32).reshape(-1)
        got["ret"] = np.asarray(ret, np.float32).reshape(-1)
        if k < n_env:
            got["env"] = {f: np.asarray(v, np.float32)
                          for f, v in setup.episode(traj["act"]).items()}
            got["metrics"] = forced.update(ep["batch"], ku)
            free.update(ep["batch"], ku)
            got.update(free.state())
        out.append(got)
    out[0]["p0"] = p0
    return out


def control(record: dict, cfg: dict, traffic: dict, seed: int,
            dtype=jnp.bfloat16, setup: Setup = None) -> dict:
    """A record as the reference computed in ``dtype`` makes it, under the
    actions of ``record``: trajectory, log-probabilities, batch, update."""
    eps = record["episodes"]
    if setup is None:
        setup = Setup(cfg, traffic, eps[0]["traj"]["act"].shape[0], dtype)
    lrn = Learner(cfg, traffic, setup, seed)
    f32 = lambda x: np.asarray(jnp.asarray(x, jnp.float32))  # noqa: E731
    out = []
    for ep in eps:
        _, ku = lrn.next_keys()
        act = ep["traj"]["act"]
        traj = {k: f32(v) for k, v in setup.episode(act).items()}
        traj["act"] = act
        traj["logp"] = f32(lrn.logp(traj["obs"], act))
        adv, ret = lrn.advantages(traj)
        n, t = act.shape[:2]
        batch = {"obs": traj["obs"].reshape(n * t, -1),
                 "act": act.reshape(n * t, -1),
                 "logp_old": traj["logp"].reshape(-1),
                 "adv": f32(adv).reshape(-1), "ret": f32(ret).reshape(-1),
                 "valid": traj["valid"].reshape(-1)}
        metrics = lrn.update(batch, ku)
        out.append({"traj": traj, "batch": batch, "metrics": metrics,
                    **lrn.state()})
    return {"episodes": out}
