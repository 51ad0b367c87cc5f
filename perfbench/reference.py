"""Plain reference of the BottleMod semantics the benchmark's deployments use.

It imports nothing of the system under test.  It reads the same
configuration file (``configs/<name>.json``) and solves one scenario at a
time, exactly and event by event, for the function class those files use:

* data requirements ``stream`` (progress proportional to bytes read) and
  ``burst`` (all input before any progress), identity outputs;
* resource requirements ``stream`` (a constant amount per unit of progress);
* resource allocations piecewise constant, external data inputs piecewise
  linear.

Within that class the data ceiling ``D(t) = min_k R_Dk(I_Dk(t))`` is
piecewise linear and the resource-limited speed ``s(t) = min_l I_Rl(t) / r_l``
piecewise constant, so progress follows ``s`` until it meets the ceiling and
then follows the ceiling while its slope is no larger than ``s`` (BottleMod,
arXiv:2209.05358, Sect. 3, eq. 1-6).  A process starts when every process it
is gated on has finished.  The time a process spends following its ceiling
is attributed to ``data``, the rest to ``resource``.

``dtype`` sets the precision of every value and every operation: float64 is
the reference, float32 is the control that the comparison must reject.
"""

from __future__ import annotations

import math

import numpy as np

INF = math.inf


class PL:
    """Right-continuous piecewise-linear function of time: on
    ``[t[i], t[i+1])`` its value is ``v[i] + s[i] * (x - t[i])``."""

    def __init__(self, t, v, s, dt):
        self.dt = dt
        self.t = np.asarray(t, dt)
        self.v = np.asarray(v, dt)
        self.s = np.asarray(s, dt)

    def piece(self, x) -> int:
        return max(int(np.searchsorted(self.t, x, side="right")) - 1, 0)

    def value(self, x):
        i = self.piece(x)
        return self.v[i] + self.s[i] * (self.dt(x) - self.t[i])

    def scaled(self, k) -> "PL":
        k = self.dt(k)
        return PL(self.t, self.v * k, self.s * k, self.dt)

    def sped_up(self, f) -> "PL":
        """``I(f * t)``: the same data arriving ``f`` times faster."""
        f = self.dt(f)
        return PL(self.t / f, self.v, self.s * f, self.dt)

    def first_at_or_above(self, y):
        """First time the function reaches ``y`` (it is non-decreasing)."""
        y = self.dt(y)
        for i in range(len(self.t)):
            if self.v[i] >= y:
                return self.t[i]
            if self.s[i] > 0:
                tc = self.t[i] + (y - self.v[i]) / self.s[i]
                if i + 1 == len(self.t) or tc < self.t[i + 1]:
                    return tc
        return INF


def step_fn(starts, rates, dt) -> PL:
    return PL(starts, rates, np.zeros(len(starts)), dt)


class Reference:
    """One deployment (a parsed configuration file) in a given precision."""

    def __init__(self, config: dict, dtype=np.float64):
        self.dt = dtype
        self.procs = {p["name"]: p for p in config["processes"]}
        self.order = _topo_order(config["processes"])

    # -- inputs --------------------------------------------------------------
    def base_alloc(self, proc: str, res: str) -> PL:
        r = _by_name(self.procs[proc]["resources"], res)
        return step_fn(r["alloc"]["starts"], r["alloc"]["rates"], self.dt)

    def base_input(self, proc: str, dep: str) -> PL:
        d = _by_name(self.procs[proc]["data"], dep)["input"]
        return PL(d["starts"], d["values"], d["slopes"], self.dt)

    def solve(self, overrides: dict) -> dict:
        """Solve one scenario.  ``overrides`` maps ``"proc.input"`` to
        ``("set", starts, rates)`` (a piecewise-constant allocation) or
        ``("scale", x)`` (allocation times ``x``; data ``x`` times faster).
        Returns ``{"makespan", "finish": {proc}, "share": {(proc, kind)}}``."""
        dt = self.dt
        finish, prog, share = {}, {}, {}
        for name in self.order:
            p = self.procs[name]
            t0 = dt(0.0)
            for g in p.get("start_after", []):
                t0 = max(t0, finish[g])
            ceils = []
            for d in p["data"]:
                key = f"{name}.{d['name']}"
                if d.get("from"):
                    avail = prog[d["from"]]
                else:
                    avail = self.base_input(name, d["name"])
                    if key in overrides:
                        avail = avail.sped_up(overrides[key][1])
                total = dt(p["total_progress"])
                if d["kind"] == "stream":
                    ceils.append(avail.scaled(total / dt(d["input_bytes"])))
                elif d["kind"] == "burst":
                    tb = avail.first_at_or_above(d["input_bytes"])
                    ceils.append(PL([0.0, tb] if tb > 0 else [0.0],
                                    [0.0, total] if tb > 0 else [total],
                                    [0.0, 0.0] if tb > 0 else [0.0], dt))
                else:
                    raise ValueError(f"unknown data requirement {d['kind']!r}")
            caps = []
            for r in p["resources"]:
                key = f"{name}.{r['name']}"
                alloc = self.base_alloc(name, r["name"])
                ov = overrides.get(key)
                if ov is not None and ov[0] == "set":
                    alloc = step_fn(ov[1], ov[2], dt)
                elif ov is not None:
                    alloc = alloc.scaled(ov[1])
                caps.append((alloc, dt(r["amount"]) / dt(p["total_progress"])))
            f, P, sh = solve_process(t0, ceils, caps,
                                     dt(p["total_progress"]), dt)
            finish[name], prog[name] = f, P
            share[(name, "data")], share[(name, "resource")] = sh
        return {"makespan": max(finish.values()), "finish": finish,
                "share": share}


def solve_process(t0, ceils: list, caps: list, p_end, dt):
    """Progress of one process from ``t0``: returns ``(finish, P, (data_s,
    resource_s))`` with ``P`` the progress function (held at ``p_end``)."""
    grid = {t0}
    for c in ceils:
        grid.update(x for x in c.t.tolist() if x > t0)
    for alloc, _r in caps:
        grid.update(x for x in alloc.t.tolist() if x > t0)
    grid = sorted(dt(x) for x in grid)
    tol = dt(1e-9) * max(dt(1.0), p_end)
    p = dt(0.0)
    pieces_t, pieces_v, pieces_s = [], [], []
    shares = {"data": dt(0.0), "resource": dt(0.0)}

    def emit(x, v, s, until, kind):
        pieces_t.append(x)
        pieces_v.append(v)
        pieces_s.append(s)
        shares[kind] += until - x

    for j, a in enumerate(grid):
        b = grid[j + 1] if j + 1 < len(grid) else dt(INF)
        if caps:
            s = min(alloc.value(a) / r for alloc, r in caps)
        else:
            s = dt(INF)
        lines = [(c.value(a), c.s[c.piece(a)]) for c in ceils]
        x = a
        while x < b:
            # lower envelope of the ceilings on [x, b): the line lowest at x,
            # and where another line with a smaller slope dips below it
            vals = [v + sl * (x - a) for v, sl in lines]
            k = min(range(len(lines)), key=lambda i: (vals[i], lines[i][1]))
            dval, d = vals[k], lines[k][1]
            xe = b
            for i, (_v, sl) in enumerate(lines):
                if sl < d and vals[i] > dval:
                    xc = x + (vals[i] - dval) / (d - sl)
                    if xc < xe:
                        xe = xc
            if p >= dval - tol and d <= s * dt(1.0 + 1e-12):
                # on the ceiling and it rises no faster than resources allow
                p = min(p, dval)
                if dval >= p_end - tol:
                    return _done(x, p_end, pieces_t, pieces_v, pieces_s,
                                 shares, dt)
                tf = x + (p_end - dval) / d if d > 0 else dt(INF)
                if tf < xe:
                    emit(x, dval, d, tf, "data")
                    return _done(tf, p_end, pieces_t, pieces_v, pieces_s,
                                 shares, dt)
                emit(x, dval, d, xe, "data")
                p = dval + d * (xe - x)
                x = xe
                continue
            if math.isinf(s):        # no resource limit: jump to the ceiling
                p = dval
                continue
            tf = x + (p_end - p) / s if s > 0 else dt(INF)
            th = x + (dval - p) / (s - d) if (s > d and p < dval) else dt(INF)
            if tf <= min(th, xe):
                emit(x, p, s, tf, "resource")
                return _done(tf, p_end, pieces_t, pieces_v, pieces_s,
                             shares, dt)
            if th < xe:
                emit(x, p, s, th, "resource")
                p = dval + d * (th - x)
                x = th
                continue
            emit(x, p, s, xe, "resource")
            p = p + s * (xe - x)
            x = xe
    return (dt(INF), PL(pieces_t or [t0], pieces_v or [0.0],
                        pieces_s or [0.0], dt),
            (shares["data"], shares["resource"]))


def _done(tf, p_end, ts, vs, ss, shares, dt):
    ts, vs, ss = ts + [tf], vs + [p_end], ss + [dt(0.0)]
    return tf, PL(ts, vs, ss, dt), (shares["data"], shares["resource"])


def _by_name(items: list, name: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(name)


def _topo_order(procs: list) -> list:
    deps = {p["name"]: {d["from"] for d in p["data"] if d.get("from")}
            | set(p.get("start_after", [])) for p in procs}
    order, done = [], set()
    while len(order) < len(procs):
        ready = [n for n in deps if n not in done and deps[n] <= done]
        if not ready:
            raise ValueError("the configuration has a cycle")
        for n in ready:
            order.append(n)
            done.add(n)
    return order
