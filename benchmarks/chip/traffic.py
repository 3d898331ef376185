"""The one traffic generator: a traffic file's parameters -> a seeded,
open-loop stream of aggregate queries with a HAVING threshold.

A traffic file (``traffic/<mix>.json``) names query templates over the
configuration's tables (a template may join its table to another one:
``"join": {"right", "left_key", "right_key"}``), their popularity (Zipf over
the listed order; s = 0 is uniform), the HAVING threshold as a quantile
range of each template's group aggregates, the quantile the set-up captures
at (``null``: no captures), and the offered rate.  An ``"engine"`` object,
where present, is laid over the configuration's engine settings (the
harness's ``Cell.engine``).

Every seed gets the same work: the number of queries per template
(largest remainder of the Zipf shares), the sequence in which the templates
arrive and the inter-arrival gaps (the quantiles of an exponential at the
offered rate, in an order drawn once from the file's ``schedule_seed``,
scaled to end the arrivals inside the window) are fixed by the file and the
window.  The seed makes the data and orders each template's threshold
quantiles (evenly spaced over the range) among that template's arrivals.
A hit scans the instance captured in set-up whatever its threshold, so two
seeds differ in the answers they check, not in the queueing the window holds.

Thresholds are quantiles of each template's exact group aggregates from
the plain reference (``reference.py``); the stream keeps them for the check.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from benchmarks.chip import reference


class Join(NamedTuple):
    """Inner equi-join of the template's table with ``right`` on
    ``left_key = right_key``; the right key is unique."""

    right: str
    left_key: str
    right_key: str


@dataclasses.dataclass(frozen=True)
class Template:
    name: str
    table: str
    groupby: Tuple[str, ...]
    agg_fn: str
    agg_attr: Optional[str]
    join: Optional[Join] = None


@dataclasses.dataclass
class Stream:
    templates: List[Template]
    op: str
    setup: List[Tuple[int, float]]  # (template, threshold) captured in set-up
    warmup: List[Tuple[int, float]]  # (template, threshold) run before the window
    queries: List[Tuple[int, float]]  # (template, threshold) in arrival order
    due: np.ndarray  # seconds after the window opens, ascending
    rate: float
    exact: List[reference.ExactGroups]  # every group of each template


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def templates(traffic: dict) -> List[Template]:
    out = []
    for t in traffic["templates"]:
        join = Join(**t["join"]) if "join" in t else None
        out.append(Template(name=t["name"], table=t["table"], groupby=tuple(t["groupby"]),
                            agg_fn=t["agg"][0], agg_attr=t["agg"][1], join=join))
    return out


def _largest_remainder(total: int, weights: np.ndarray) -> np.ndarray:
    share = weights / weights.sum() * total
    counts = np.floor(share).astype(int)
    rest = total - counts.sum()
    counts[np.argsort(-(share - counts), kind="stable")[:rest]] += 1
    return counts


def build(traffic: dict, tables: Dict[str, Dict[str, np.ndarray]], seed: int,
          seconds: float, rate: Optional[float] = None,
          exact: Optional[List[reference.ExactGroups]] = None) -> Stream:
    """The stream of one run: ``rate`` overrides the file's, and ``exact``
    reuses the group aggregates of an earlier stream on the same tables (the
    sweep)."""
    rng = np.random.default_rng([int(seed), 1])
    tmpl = templates(traffic)
    rate = float(traffic["rate_qps"] if rate is None else rate)
    lo, hi = traffic["having"]["quantile"]
    op = traffic["having"]["op"]
    if exact is None:
        exact = [reference.exact_groups(t, tables) for t in tmpl]

    def threshold(j: int, q: float) -> float:
        return float(np.quantile(exact[j].values, q))

    n = max(1, int(round(rate * seconds)))
    zipf = 1.0 / np.arange(1, len(tmpl) + 1) ** traffic["popularity"]["zipf_s"]
    per_template = _largest_remainder(n, zipf)
    schedule = np.random.default_rng([int(traffic["schedule_seed"]), 1])
    order = np.repeat(np.arange(len(tmpl)), per_template)[schedule.permutation(n)]
    queries: List[Tuple[int, float]] = [(0, 0.0)] * n
    for j, k in enumerate(per_template):
        qs = lo + (hi - lo) * (np.arange(k) + 0.5) / max(k, 1)
        for i, q in zip(np.flatnonzero(order == j), qs[rng.permutation(k)]):
            queries[i] = (j, threshold(j, q))

    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = gaps[schedule.permutation(n)]
    due = np.cumsum(gaps)
    due *= (seconds - 0.5 / rate) / due[-1]

    setup_q = traffic.get("setup_capture_quantile")
    setup = [] if setup_q is None else [(j, threshold(j, setup_q))
                                        for j in range(len(tmpl))]
    # Warm-up: every template through the path the window takes, alone and
    # several to a batch, at thresholds inside the window's range.
    warm_q = [lo + (hi - lo) * f for f in (0.25, 0.75)]
    warmup = [(j, threshold(j, q)) for q in warm_q for j in range(len(tmpl))]
    return Stream(templates=tmpl, op=op, setup=setup, warmup=warmup,
                  queries=queries, due=due, rate=rate, exact=exact)
