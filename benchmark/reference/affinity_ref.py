"""Inter-pod affinity, anti-affinity and the soft topology spread, in plain
NumPy: what a bound backlog may not show, where ONE pending pod may go on a
standing cluster, and where it must go.

Written from the published semantics (Kubernetes' inter-pod affinity
predicate as Volcano's predicates plugin applies it, ``predicates.go``), not
from the program's code:

- a *term* is a label selector and a topology key.  A node's *domain* under
  the key ``kubernetes.io/hostname`` is the node itself, under any other key
  the value of that node label (here: the zone; a node without the label has
  no domain, satisfies no affinity and violates no anti-affinity);
- *required affinity*: the node's domain must hold a resident pod the
  selector matches.  The first-pod rule: a term that matches no resident
  anywhere is satisfied on every node by a pod that matches it itself
  (else the first pod of a self-affine gang could never be placed);
- *required anti-affinity*, both directions: the node's domain may hold no
  resident the pod's term matches, and no resident one of whose own
  anti-affinity terms matches the pod;
- *soft topology spread* ``(key, weight)``: a node scores ``-weight`` for
  every pod of the pending pod's own job already placed in the node's domain
  (``api/spec.py``: "softly prefer domains with fewer pods of this pod's own
  job"; ``ops/wave.py`` adds ``t_soft x count`` to the node score with
  ``t_soft = -weight``).  It constrains nothing.

Namespaces are left out: every pod of the benchmark lives in ``default`` and
a term without namespaces means the pod's own.  Integers and float64; this
file imports nothing of the program and takes nothing it made.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from . import score_ref

HOSTNAME = "kubernetes.io/hostname"


class Term(NamedTuple):
    selector: Tuple[Tuple[str, str], ...]     # label pairs, all must match
    key: str                                  # HOSTNAME or a node label key


class Resident(NamedTuple):
    """A pod bound to ``node`` (an index), with what it carries."""

    node: int
    labels: Dict[str, str]
    job: str = ""
    anti_affinity: Tuple[Term, ...] = ()


def term(labels: Dict[str, str], key: str) -> Term:
    return Term(tuple(sorted(labels.items())), key)


def _matches(t: Term, labels: Dict[str, str]) -> bool:
    return all(labels.get(k) == v for k, v in t.selector)


def _domain(node_zone: np.ndarray, key: str) -> np.ndarray:
    """[N] domain id of every node under ``key``, -1 where it has none."""
    node_zone = np.asarray(node_zone, dtype=np.int64)
    if key == HOSTNAME:
        return np.arange(len(node_zone), dtype=np.int64)
    return node_zone


# ---- (a) what a bound backlog may not show --------------------------------


def violations(gang_kind: Sequence[str], pod_gang, pod_node, node_zone) -> dict:
    """Counts over one plan's binds.  ``gang_kind[g]`` is ``"affinity"``
    (zone), ``"anti_affinity"`` (hostname), ``"spread"`` or ``""``;
    ``pod_gang[i]`` the gang of pod ``i``; ``pod_node[i]`` the index of the
    node it was bound to, -1 if none; ``node_zone[n]`` the zone of node
    ``n``.  Every term selects the gang's own label, so:

    - ``affinity_outside``: pods of an affinity gang bound outside the
      zone that holds most of their gang (the lowest zone among ties);
    - ``anti_shared``: pods of an anti-affinity gang bound to a node that
      holds another pod of their gang;
    - ``spread_zones``: per spread gang, the zones its bound pods cover
      (reported, not limited: the term is soft).

    Unbound pods are counted by nobody here (the validator counts them)."""
    kinds = np.asarray(list(gang_kind), dtype=object)
    pod_gang = np.asarray(pod_gang, dtype=np.int64)
    pod_node = np.asarray(pod_node, dtype=np.int64)
    node_zone = np.asarray(node_zone, dtype=np.int64)
    bound = pod_node >= 0
    zones = int(node_zone.max()) + 1 if len(node_zone) else 0
    g_n = len(kinds)
    zone_of = np.where(bound, node_zone[np.maximum(pod_node, 0)], -1)

    out = {"affinity_pods": 0, "affinity_outside": 0, "anti_pods": 0,
           "anti_shared": 0, "spread_gangs": 0,
           "spread_zones": np.zeros(0, np.int64)}

    is_aff = (kinds == "affinity")[pod_gang] & bound
    out["affinity_pods"] = int(is_aff.sum())
    if is_aff.any():
        per = np.zeros((g_n, max(zones, 1)), np.int64)
        np.add.at(per, (pod_gang[is_aff], zone_of[is_aff]), 1)
        home = per.argmax(axis=1)               # lowest zone among ties
        out["affinity_outside"] = int(
            (zone_of[is_aff] != home[pod_gang[is_aff]]).sum())

    is_anti = (kinds == "anti_affinity")[pod_gang] & bound
    out["anti_pods"] = int(is_anti.sum())
    if is_anti.any():
        pair = pod_gang[is_anti] * np.int64(len(node_zone)) + pod_node[is_anti]
        _, inv, cnt = np.unique(pair, return_inverse=True, return_counts=True)
        out["anti_shared"] = int((cnt[inv] > 1).sum())

    spread = np.flatnonzero(kinds == "spread")
    out["spread_gangs"] = int(len(spread))
    if len(spread):
        is_sp = (kinds == "spread")[pod_gang] & bound
        seen = np.zeros((g_n, max(zones, 1)), bool)
        seen[pod_gang[is_sp], zone_of[is_sp]] = True
        out["spread_zones"] = seen[spread].sum(axis=1).astype(np.int64)
    return out


def check(events, nodes, config) -> Dict[str, int]:
    """The configuration's check (``guarantees.checks: ["affinity"]``, the slot of
    the benchmark's ``checks.py``): ``violations`` over every round of the run that
    dealt a kind, from the round's plan, the binds seen in it and the nodes'
    ``zone`` labels.  The two counts that are guarantees; a pod not bound in
    its round is counted by the validator, and here by nobody."""
    index = {name: i for i, name in enumerate(nodes["names"])}
    zone_ids: Dict[str, int] = {}
    node_zone = np.array([zone_ids.setdefault(labels["zone"], len(zone_ids))
                          if "zone" in labels else -1
                          for labels in nodes["labels"]], dtype=np.int64)
    out = {"affinity_outside": 0, "anti_shared": 0}
    for ev in events:
        plan = ev.plan
        if not any(plan.gang_kind):
            continue
        host = {}
        for _t, keys, hosts in ev.arrivals:
            host.update(zip(keys, hosts))
        pod_node = np.array([index.get(host.get(key), -1)
                             for key in plan.keys()], dtype=np.int64)
        v = violations(plan.gang_kind, plan.gang, pod_node, node_zone)
        out["affinity_outside"] += v["affinity_outside"]
        out["anti_shared"] += v["anti_shared"]
    return out


# ---- (b) where one pending pod may go --------------------------------------


def allowed(node_zone, residents: Sequence[Resident], labels: Dict[str, str],
            affinity: Sequence[Term] = (),
            anti_affinity: Sequence[Term] = ()) -> np.ndarray:
    """[N] bool: the nodes a pending pod with ``labels`` and these required
    terms may be bound to, given the pods standing on the cluster."""
    node_zone = np.asarray(node_zone, dtype=np.int64)
    n = len(node_zone)
    ok = np.ones(n, bool)

    def holds(t: Term) -> np.ndarray:
        """[N] bool: the node's domain under ``t.key`` holds a resident
        that ``t`` matches."""
        dom = _domain(node_zone, t.key)
        hit = {int(dom[r.node]) for r in residents
               if _matches(t, r.labels) and dom[r.node] >= 0}
        return np.isin(dom, list(hit)) & (dom >= 0)

    for t in affinity:
        matched_anywhere = any(_matches(t, r.labels) for r in residents)
        if not matched_anywhere and _matches(t, labels):
            continue                            # the first-pod rule
        ok &= holds(t)
    for t in anti_affinity:
        ok &= ~holds(t)
    for r in residents:                         # their terms against the pod
        for t in r.anti_affinity:
            if _matches(t, labels):
                dom = _domain(node_zone, t.key)
                if dom[r.node] >= 0:
                    ok &= dom != dom[r.node]
    return ok


# ---- (c) the soft spread, and the one node it names -------------------------


def spread_scores(node_zone, residents: Sequence[Resident], job: str,
                  spread: Sequence[Tuple[str, float]]) -> np.ndarray:
    """[N] float64: for each ``(key, weight)`` of the pod's topology
    spread, ``-weight`` times the pods of its own ``job`` standing in the
    node's domain under ``key``."""
    node_zone = np.asarray(node_zone, dtype=np.int64)
    out = np.zeros(len(node_zone), np.float64)
    mates = [r.node for r in residents if job and r.job == job]
    for key, weight in spread:
        dom = _domain(node_zone, key)
        mate_dom = dom[np.asarray(mates, dtype=np.int64)] if mates else dom[:0]
        mate_dom = mate_dom[mate_dom >= 0]
        width = int(dom.max()) + 1 if len(dom) else 0
        per = np.bincount(mate_dom, minlength=max(width, 1))
        out -= float(weight) * np.where(dom >= 0, per[np.maximum(dom, 0)], 0)
    return out


def choose(alloc, used, req, may: Optional[np.ndarray] = None,
           extra: Optional[np.ndarray] = None) -> int:
    """Index of the node the pod must go to: among the nodes that fit
    (``score_ref.feasible``) and that ``may`` allows, the highest
    ``score_ref.scores`` + ``extra`` (the spread term), lowest index among
    ties; -1 when there is none."""
    ok = score_ref.feasible(alloc, used, req)
    if may is not None:
        ok &= np.asarray(may, bool)
    if not ok.any():
        return -1
    s = score_ref.scores(alloc, used, req)
    if extra is not None:
        s = s + np.asarray(extra, np.float64)
    s = np.where(ok, s, -np.inf)
    return int(np.flatnonzero(s >= s.max() - score_ref.TIE)[0])
