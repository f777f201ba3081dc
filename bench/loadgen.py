"""The one traffic generator: it reads a mix's parameters from
`bench/traffic/<mix>.json` and drives the serving driver with them.

Two loops, chosen by the mix's "loop":

* "closed": `tenants` callers, each submitting its next session as soon
  as its last one finished.  Tenant i's first session has budget
  `first_budgets[i]`, later ones draw from `budgets`.
* "open": sessions due at Poisson arrival times of `rate_per_s`, submitted
  on schedule whatever the backlog, between the driver's ticks.  Every
  seed gets the same multiset of sessions (budget x rule x data size, in
  the mix's proportions) and the same multiset of inter-arrival gaps,
  each in its own order, so the seed changes the data and the order and
  never the amount of work.

A session is a dict: its index, tenant, due time (s from the window's
start), iteration budget, combine rule ("dsvb" or "admm"), tau, points
per node and pool entry.  A mix also names the driver's `max_fleet` and
`slice_iters`, its compute `backend`, the `pool` of datasets made from
the seed, the `sample` of answers compared, and may trace only the last
`trace_seconds` of the window.
"""
from __future__ import annotations

import itertools
import math
import time

import numpy as np

from bench import data as data_lib


def _largest_remainder(weights: list, total: int) -> list:
    raw = [w * total / sum(weights) for w in weights]
    counts = [math.floor(r) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: (counts[i] - raw[i], i))
    for i in order[:total - sum(counts)]:
        counts[i] += 1
    return counts


def kinds(traffic: dict) -> list:
    """[(rule, tau)], each rule an equal share, its taus splitting it."""
    out = []
    for r in traffic["rules"]:
        out.append([(r["rule"], float(t)) for t in r.get("taus", [0.0])])
    return out


def _budgets(traffic: dict) -> tuple:
    b = traffic["budgets"]
    return [int(k) for k in b], [float(v) for v in b.values()]


def pool_sizes(traffic: dict, config: dict) -> list:
    """Points per node of each pool entry, cycling the config's sizes."""
    sizes = config["points_per_node"]
    return [sizes[i % len(sizes)] for i in range(traffic["pool"])]


def _entry(g, sizes_of_pool, size) -> int:
    entries = [i for i, s in enumerate(sizes_of_pool) if s == size]
    return int(entries[g.integers(len(entries))])


def open_sessions(traffic: dict, config: dict, seed: int,
                  seconds: float) -> list:
    """The open loop's sessions due in a window of `seconds`."""
    m = max(1, round(traffic["rate_per_s"] * seconds))
    budgets, probs = _budgets(traffic)
    rules = kinds(traffic)
    sizes = config["points_per_node"]
    combos = list(itertools.product(range(len(budgets)), range(len(rules)),
                                    sizes))
    counts = _largest_remainder([probs[b] for b, _, _ in combos], m)
    plan = []
    for (b, k, size), c in zip(combos, counts):
        for j in range(c):
            rule, tau = rules[k][j % len(rules[k])]
            plan.append(dict(budget=budgets[b], rule=rule, tau=tau,
                             size=size))
    g = data_lib.rng(seed, 3)
    g.shuffle(plan)
    # the same m exponential quantiles in every run, in the seed's order
    gaps = -np.log1p(-(np.arange(m) + 0.5) / m) / traffic["rate_per_s"]
    g.shuffle(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    pool = pool_sizes(traffic, config)
    for i, s in enumerate(plan):
        s.update(index=i, tenant=None, due=float(due[i]),
                 entry=_entry(g, pool, s["size"]))
    return plan


class ClosedTenants:
    """The closed loop's callers: `next(i)` is tenant i's next session."""

    def __init__(self, traffic: dict, config: dict, seed: int):
        self.traffic = traffic
        self.budgets = _budgets(traffic)
        self.kinds = [k for ks in kinds(traffic) for k in ks]
        self.pool = pool_sizes(traffic, config)
        self.g = data_lib.rng(seed, 4)
        self.made = [0] * traffic["tenants"]
        self.count = itertools.count()

    def next(self, tenant: int) -> dict:
        first = self.made[tenant] == 0
        self.made[tenant] += 1
        budgets, probs = self.budgets
        budget = (self.traffic["first_budgets"][tenant] if first else
                  budgets[self.g.choice(len(budgets),
                                        p=np.asarray(probs) / sum(probs))])
        rule, tau = self.kinds[self.g.integers(len(self.kinds))]
        size = self.pool[self.g.integers(len(self.pool))]
        return dict(index=next(self.count), tenant=tenant, due=None,
                    budget=int(budget), rule=rule, tau=tau, size=size,
                    entry=_entry(self.g, self.pool, size))


def closed_loop(driver, tenants: ClosedTenants, submit, seconds: float,
                annotate, profile) -> dict:
    """Run the closed loop for `seconds` from now, ticking the driver in
    this thread.  `submit(spec)` returns the session id; `profile(now)` is
    told the time into the window between ticks.  Returns the window: its
    start and end, every session with its submit and finish times, and
    the session-iterations done inside it."""
    records = []
    t0 = time.perf_counter()
    profile(0.0)
    current = {}
    for i in range(tenants.traffic["tenants"]):
        with annotate("bench/submit"):
            current[i] = _submit(records, submit, tenants.next(i), t0)
    while True:
        with annotate("bench/tick"):
            driver.tick()
        now = time.perf_counter()
        with annotate("bench/poll"):
            done = [i for i, rec in current.items()
                    if driver.status(rec["rid"]).done]
        for i in done:
            current[i]["finish"] = now - t0
        if now - t0 >= seconds:
            break
        profile(now - t0)
        for i in done:
            with annotate("bench/submit"):
                current[i] = _submit(records, submit, tenants.next(i), t0)
    t1 = time.perf_counter()
    iters = sum(driver.status(r["rid"]).t for r in records)
    return dict(start=t0, end=t1, seconds=t1 - t0, records=records,
                iters=iters)


def open_loop(driver, sessions: list, submit, seconds: float,
              annotate, profile) -> dict:
    """Submit each session once it is due, whatever the backlog, and tick
    the driver in this thread between submissions (sleeping only while
    nothing is open); returns the window."""
    records = []
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - t0
        profile(now)
        while i < len(sessions) and sessions[i]["due"] <= now:
            with annotate("bench/submit"):
                _submit(records, submit, sessions[i], t0)
            i += 1
            now = time.perf_counter() - t0
        if now >= seconds:
            break
        if driver.remaining():
            with annotate("bench/tick"):
                driver.tick()
        else:
            nxt = sessions[i]["due"] if i < len(sessions) else seconds
            with annotate("bench/sleep"):
                time.sleep(max(0.0, min(nxt, seconds) - now))
    t1 = time.perf_counter()
    return dict(start=t0, end=t1, seconds=t1 - t0, records=records)


def drain(driver, window: dict, drain_s: float) -> None:
    """Tick on for up to `drain_s` past the window until every session
    due in it has finished.  A session's finish is the driver's finish
    stamp, read as its submit-return time plus the latency the driver
    reports (an overestimate by the admission that follows the stamp)."""
    deadline = window["end"] + drain_s
    while driver.remaining() and time.perf_counter() < deadline:
        driver.tick()
    window["gave_up"] = time.perf_counter() - window["start"]
    for r in window["records"]:
        st = driver.status(r["rid"])
        if st.done:
            r["finish"] = r["submitted"] + st.latency_s


def _submit(records, submit, spec, t0) -> dict:
    t_call = time.perf_counter() - t0
    rid = submit(spec)
    t_ret = time.perf_counter() - t0
    rec = dict(spec=spec, rid=rid, called=t_call, submitted=t_ret,
               finish=None)
    records.append(rec)
    return rec
