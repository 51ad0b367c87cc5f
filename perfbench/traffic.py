"""The one load generator: a traffic file (``traffic/<name>.json``) and a
seed in, plain request data out.  Nothing here imports the system under
test; :mod:`deploy` turns the output into program objects.

Open loop (``"loop": "open"``): arrivals of independent clients, Poisson at
``rate_per_s`` on average, modulated by bursts (``factor`` times the base
rate for ``seconds`` in every ``period_s``, at a phase drawn from the seed).
Request sizes follow a truncated Zipf law.  Every seed gets the same work:
the same number of requests, the same multiset of sizes and of gaps
(exponential quantiles), and the same count of each scenario kind, in a
different order and with different scenario parameters.

A scenario kind (an entry of ``"mix"``) draws named values and turns them
into overrides of one deployment's inputs: ``"set"`` replaces an allocation
by a step function, ``"scale"`` multiplies an allocation (or, for an
external data input, speeds its arrival up) by a factor.  Keys may be glob
patterns over the deployment's ``proc.input`` keys; values are arithmetic
expressions over the drawn values and the configuration's ``constants``.
"""

from __future__ import annotations

import ast
import fnmatch
import itertools
import operator

import numpy as np

_OPS = {ast.Add: operator.add, ast.Sub: operator.sub,
        ast.Mult: operator.mul, ast.Div: operator.truediv}


def evaluate(expr, names: dict) -> float:
    """An arithmetic expression (``+ - * /``, unary minus, numbers, names)."""
    if isinstance(expr, (int, float)):
        return float(expr)

    def ev(node):
        if isinstance(node, ast.Expression):
            return ev(node.body)
        if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
            return float(node.value)
        if isinstance(node, ast.Name):
            return float(names[node.id])
        if isinstance(node, ast.BinOp) and type(node.op) in _OPS:
            return _OPS[type(node.op)](ev(node.left), ev(node.right))
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return -ev(node.operand)
        raise ValueError(f"unsupported expression {expr!r}")

    return ev(ast.parse(str(expr), mode="eval"))


def input_keys(config: dict) -> list:
    """Every overridable ``proc.input`` key of a deployment."""
    keys = []
    for p in config["processes"]:
        keys += [f"{p['name']}.{r['name']}" for r in p["resources"]]
        keys += [f"{p['name']}.{d['name']}" for d in p["data"]
                 if not d.get("from")]
    return keys


def _expand(pattern: str, keys: list) -> list:
    hit = [k for k in keys if fnmatch.fnmatchcase(k, pattern)]
    if not hit:
        raise ValueError(f"pattern {pattern!r} matches no input")
    return hit


def draw(spec, rng):
    family, *params = spec
    if family == "uniform":
        return float(rng.uniform(*params))
    raise ValueError(f"unknown draw family {family!r}")


class Mix:
    """The scenario kinds of a traffic file, bound to one deployment."""

    def __init__(self, traffic: dict, config: dict):
        self.kinds = traffic["mix"]
        w = np.array([k["weight"] for k in self.kinds], np.float64)
        self.p = w / w.sum()
        self.consts = config.get("constants", {})
        self.keys = input_keys(config)

    def scenario(self, kind: int, rng) -> dict:
        k = self.kinds[kind]
        names = dict(self.consts)
        for var, spec in k.get("draw", {}).items():
            names[var] = draw(spec, rng)
        out = {}
        for pat, fn in k.get("set", {}).items():
            starts = [evaluate(e, names) for e in fn["starts"]]
            rates = [evaluate(e, names) for e in fn["rates"]]
            for key in _expand(pat, self.keys):
                out[key] = ("set", starts, rates)
        for pat, e in k.get("scale", {}).items():
            x = evaluate(e, names)
            for key in _expand(pat, self.keys):
                out[key] = ("scale", x)
        return out


def _zipf_quantiles(n: int, s: float, lo: int, hi: int) -> np.ndarray:
    ks = np.arange(lo, hi + 1)
    cdf = np.cumsum(ks ** -s)
    cdf /= cdf[-1]
    q = (np.arange(n) + 0.5) / n
    return ks[np.searchsorted(cdf, q)]


def _counts(p: np.ndarray, total: int) -> np.ndarray:
    """Largest-remainder apportionment of ``total`` by shares ``p``."""
    raw = p * total
    c = np.floor(raw).astype(int)
    c[np.argsort(-(raw - c), kind="stable")[:total - c.sum()]] += 1
    return c


def open_loop(traffic: dict, config: dict, seed: int, seconds: float):
    """The window's requests: a list of ``(due_s, [scenario, ...])`` sorted
    by due time, each scenario a dict of overrides.  Many independent
    clients merge into one Poisson stream, so clients are not told apart."""
    rng = np.random.default_rng([int(seed), 1])
    mix = Mix(traffic, config)
    n = max(int(round(traffic["rate_per_s"] * seconds)), 1)
    bst = traffic["burst"]
    duty = bst["seconds"] / bst["period_s"]
    base = traffic["rate_per_s"] / (1.0 + (bst["factor"] - 1.0) * duty)
    phase = float(rng.uniform(0.0, bst["period_s"]))

    period, bsec, fac = bst["period_s"], bst["seconds"], bst["factor"]

    def in_bursts(t):   # burst seconds in [phase - period, t]
        full, rem = divmod(t - phase + period, period)
        return full * bsec + min(rem, bsec)

    def cum(t):         # expected arrivals in [0, t]
        return base * (t + (fac - 1.0) * (in_bursts(t) - in_bursts(0.0)))

    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps) * (cum(seconds) / gaps.sum())
    targets = np.cumsum(gaps) * (1.0 - 0.5 / n)
    dues = np.array([_invert(cum, u, 0.0, seconds) for u in targets])
    rs = traffic["request_size"]
    sizes = rng.permutation(_zipf_quantiles(n, rs["zipf_s"], rs["min"], rs["max"]))
    kinds = rng.permutation(np.repeat(np.arange(len(mix.kinds)),
                                      _counts(mix.p, int(sizes.sum()))))
    out, at = [], 0
    for i in range(n):
        scs = [mix.scenario(int(k), rng) for k in kinds[at:at + sizes[i]]]
        at += sizes[i]
        out.append((float(dues[i]), scs))
    return out


def _invert(f, u, lo, hi):
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if f(mid) < u:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def warm_batches(traffic: dict, config: dict, seed: int, max_batch: int,
                 eps: float = 1e-6):
    """Batches that reach every stacked shape the window can build.

    The service pads a coalesced batch to a power of two, and which inputs a
    batch overrides is part of the compiled program's signature.  For each
    bucket ``b`` this yields one batch per set of scenario kinds that a batch
    of ``b // 2 + 1`` scenarios holds with probability above ``eps``, the
    batch with every kind first and twice (the engine's iteration budget
    tightens after the first solve of a shape)."""
    rng = np.random.default_rng([int(seed), 2])
    mix = Mix(traffic, config)
    K = len(mix.kinds)
    b = 1
    while b <= max_batch:
        k_min = b // 2 + 1
        subsets = [s for r in range(K, 0, -1)
                   for s in itertools.combinations(range(K), r)
                   if len(s) <= b and _p_exactly(mix.p, s, k_min) > eps]
        for s in [subsets[0]] + subsets:
            kinds = [s[i % len(s)] for i in range(b)]
            yield [mix.scenario(k, rng) for k in kinds]
        b *= 2


def _p_exactly(p: np.ndarray, s: tuple, k: int) -> float:
    """Probability that ``k`` i.i.d. kinds are exactly the set ``s``."""
    tot = 0.0
    for r in range(len(s) + 1):
        for sub in itertools.combinations(s, r):
            tot += (-1) ** (len(s) - r) * float(p[list(sub)].sum()) ** k
    return max(tot, 0.0)


def mc_seeds(seed: int, count: int, warm: bool = False) -> list:
    """Seeds of the Monte Carlo calls: the window's, or set-up's (disjoint)."""
    off = 1 << 20 if warm else 0
    return [(int(seed) * 1_000_003 + off + j) % (1 << 31) for j in range(count)]
