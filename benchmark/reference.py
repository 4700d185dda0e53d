"""The plain reference: what the served path must answer for the rows
a run admitted, written from the deployment's stated semantics alone.

It imports nothing of the program.  Its inputs are what the benchmark
handed the agent (client addresses, the identity given to each /32
entry, labels, the endpoints, rules) and the admitted rows in
admission order.  Per row it decides, as upstream Cilium's bpf_lxc
does for ingress to the row's endpoint:

1. identity: the /32 ipcache entry of the source address;
2. conntrack (one table per endpoint): the row's 5-tuple is
   ESTABLISHED if an earlier admitted row of that tuple was forwarded
   as NEW (which created the entry), else NEW;
3. policy (NEW rows only), the rules that select the endpoint: deny
   rules win, then allow and L7-redirect rules, else default deny;
   established rows bypass policy;
4. events: a drop is a DropNotify with its reason; a forwarded NEW row
   a PolicyVerdictNotify; a forwarded established row a TraceNotify.

Egress rows from an endpoint must answer an established ingress tuple
(a conntrack REPLY: forwarded, a trace event).  IPv4 TCP is what it
models; other rows raise, and a mix that sends them needs this
reference extended first.

Conntrack lifetimes are not modelled: every mix refreshes its entries
well inside the program's shortest lifetime (60 s for a SYN-only
entry), closes a connection with its last packet, and sends no row of
a tuple after its FIN.  Expired entries are only ever swept, never
read, so the agent's periodic conntrack GC changes no answer.

The monitor codes are the program's wire vocabulary: message types as
upstream's ``pkg/monitor/api`` numbers them (DROP 1, TRACE 4,
POLICY_VERDICT 9) and the program's drop reasons (1 explicit deny,
2 default deny; 9-11 are host-synthesized shed and recovery drops,
accounted by the ledger instead).

``control`` names a reference with one stated guarantee broken, for
the check that the comparison can fail: ``ct_hash`` keys conntrack by
a ``ct_hash_bits``-bit hash of the tuple instead of the whole tuple,
the shortcut a gather-bound conntrack tempts.
"""

from __future__ import annotations

import ipaddress
from typing import Dict, List, Optional

import numpy as np

MSG_DROP, MSG_TRACE, MSG_POLICY_VERDICT = 1, 4, 9
REASON_FORWARDED, REASON_DENY, REASON_DEFAULT_DENY = 0, 1, 2
HOST_REASONS = (9, 10, 11)
N_REASON_ROWS = 16

# policy outcomes
DEFAULT_DENY, ALLOW, REDIRECT, DENY = 0, 1, 2, 3
_PROTO = {"TCP": 6, "UDP": 17, "SCTP": 132, "ANY": None}

COL_SRC_IP3, COL_DST_IP3 = 3, 7
COL_SPORT, COL_DPORT, COL_PROTO, COL_EP, COL_DIR = 8, 9, 10, 14, 15


def _labels(tokens: List[str]) -> Dict[str, str]:
    """"k8s:app=db" -> {"app": "db", "k8s:app": "db"}."""
    out = {}
    for t in tokens:
        k, _, v = t.partition("=")
        out[k] = v
        out[k.split(":", 1)[-1]] = v
    return out


def _selects(selector: dict, labels: Dict[str, str]) -> bool:
    if set(selector) - {"matchLabels"}:
        raise ValueError(f"selector form not modelled: {selector}")
    return all(labels.get(k) == str(v)
               for k, v in selector.get("matchLabels", {}).items())


def _port_rule(to_ports: Optional[list], port: int, proto: int):
    """None when no port rule admits (port, proto); else whether the
    admitting rule carries L7 rules."""
    if not to_ports:
        return False
    hit = None
    for tp in to_ports:
        for p in tp.get("ports", []):
            lo = int(p["port"])
            hi = int(p.get("endPort", lo))
            want = _PROTO[p.get("protocol", "ANY").upper()]
            if lo <= port <= hi and (want is None or want == proto):
                l7 = bool(tp.get("rules"))
                hit = l7 if hit is None else (hit or l7)
    return hit


class Policy:
    """Ingress policy of one endpoint, evaluated rule by rule."""

    def __init__(self, rules: List[dict], endpoint_labels: List[str],
                 client_labels: List[List[str]], client_ips: np.ndarray):
        ep = _labels(endpoint_labels)
        self.rules = [r for r in rules
                      if _selects(r["endpointSelector"], ep)]
        self.enforced = any("ingress" in r or "ingressDeny" in r
                            for r in self.rules)
        self.client_labels = [_labels(lab) for lab in client_labels]
        self.client_ips = [ipaddress.IPv4Address(int(ip))
                           for ip in client_ips]
        self._tables: Dict[tuple, np.ndarray] = {}

    def _peer(self, entry: dict, i: int) -> bool:
        if "fromEndpoints" in entry and any(
                _selects(s, self.client_labels[i])
                for s in entry["fromEndpoints"]):
            return True
        return any(self.client_ips[i] in ipaddress.ip_network(c)
                   for c in entry.get("fromCIDR", ()))

    def outcome(self, i: int, port: int, proto: int) -> int:
        if not self.enforced:
            return ALLOW
        for r in self.rules:
            for e in r.get("ingressDeny", ()):
                if self._peer(e, i) and _port_rule(
                        e.get("toPorts"), port, proto) is not None:
                    return DENY
        best = DEFAULT_DENY
        for r in self.rules:
            for e in r.get("ingress", ()):
                if not self._peer(e, i):
                    continue
                l7 = _port_rule(e.get("toPorts"), port, proto)
                if l7 is not None:
                    best = REDIRECT if l7 else max(best, ALLOW)
        return best

    def table(self, port: int, proto: int) -> np.ndarray:
        key = (port, proto)
        if key not in self._tables:
            self._tables[key] = np.array(
                [self.outcome(i, port, proto)
                 for i in range(len(self.client_labels))], dtype=np.int8)
        return self._tables[key]


def tuple_keys(src, sport, dport) -> np.ndarray:
    return ((src.astype(np.uint64) << np.uint64(32))
            | (sport.astype(np.uint64) << np.uint64(16))
            | dport.astype(np.uint64))


def event_keys(src, dst, sport, dport) -> np.ndarray:
    """An event's packet: its tuple, with the destination folded in by
    a multiplicative hash (the keys are only ever compared)."""
    with np.errstate(over="ignore"):
        d = dst.astype(np.uint64) * np.uint64(0xD6E8FEB86659FD93)
    return tuple_keys(src, sport, dport) ^ d


def event_vals(identity, msg, reason) -> np.ndarray:
    return ((identity.astype(np.uint64) << np.uint64(16))
            | (msg.astype(np.uint64) << np.uint64(8))
            | reason.astype(np.uint64))


class Reference:
    """Feed admitted rows in admission order; read ``counters``
    ([reason, direction] like the program's metrics map) and the event
    multiset (``events()``)."""

    def __init__(self, rules, endpoints, client_labels, client_ips,
                 client_ids, every_event: bool,
                 control: Optional[str] = None, ct_hash_bits: int = 16):
        order = np.argsort(client_ips)
        self.ips_sorted = np.asarray(client_ips, dtype=np.uint32)[order]
        self.ids_sorted = np.asarray(client_ids, dtype=np.uint32)[order]
        self.idx_sorted = order
        self.policy = {int(ep["id"]): Policy(rules, ep["labels"],
                                             client_labels, client_ips)
                       for ep in endpoints}
        self.est = {ep: np.zeros(0, dtype=np.uint64) for ep in self.policy}
        self.every_event = every_event
        if control not in (None, "ct_hash"):
            raise ValueError(f"unknown control {control!r}")
        self.control = control
        self.hash_mask = np.uint64((1 << ct_hash_bits) - 1)
        self.counters = np.zeros((N_REASON_ROWS, 2), dtype=np.int64)
        self._keys: List[np.ndarray] = []
        self._vals: List[np.ndarray] = []
        self.rows = 0

    def _ct_key(self, key: np.ndarray) -> np.ndarray:
        if self.control == "ct_hash":
            with np.errstate(over="ignore"):
                h = key * np.uint64(0x9E3779B97F4A7C15)
            return (h >> np.uint64(40)) & self.hash_mask
        return key

    def feed(self, rows: np.ndarray) -> None:
        if not len(rows):
            return
        if np.any(rows[:, COL_PROTO] != 6):
            raise ValueError("the reference models TCP rows only")
        eps = np.unique(rows[:, COL_EP])
        if not set(eps.tolist()) <= set(self.policy):
            raise ValueError("a row names an endpoint the deployment "
                             "does not have")
        # each endpoint's conntrack sees its own rows in admission
        # order; the tables are independent
        for ep in eps.tolist():
            part = rows if len(eps) == 1 else rows[rows[:, COL_EP] == ep]
            self._feed_endpoint(int(ep), part)
        self.rows += len(rows)

    def _feed_endpoint(self, ep: int, rows: np.ndarray) -> None:
        egress = rows[:, COL_DIR] == 1
        if egress.any():
            # runs of one direction, in admission order
            cut = np.flatnonzero(np.diff(egress.astype(np.int8))) + 1
            for part in np.split(np.arange(len(rows)), cut):
                if egress[part[0]]:
                    self._feed_replies(ep, rows[part])
                else:
                    self._feed_ingress(ep, rows[part])
            return
        self._feed_ingress(ep, rows)

    def _feed_ingress(self, ep: int, rows: np.ndarray) -> None:
        src = rows[:, COL_SRC_IP3]
        pos = np.searchsorted(self.ips_sorted, src)
        pos = np.minimum(pos, len(self.ips_sorted) - 1)
        if np.any(self.ips_sorted[pos] != src):
            raise ValueError("a row's source has no /32 ipcache entry")
        client = self.idx_sorted[pos]
        identity = self.ids_sorted[pos]
        dport = rows[:, COL_DPORT]
        outcome = np.empty(len(rows), dtype=np.int8)
        for p in np.unique(dport):
            m = dport == p
            outcome[m] = self.policy[ep].table(int(p), 6)[client[m]]
        allowed = (outcome == ALLOW) | (outcome == REDIRECT)
        ck = self._ct_key(tuple_keys(src, rows[:, COL_SPORT], dport))
        # established: created before this block, or by an earlier
        # allowed row of this block (the first allowed row creates)
        est = np.isin(ck, self.est[ep])
        idx = np.arange(len(rows))
        a_ck, a_first = np.unique(ck[allowed], return_index=True)
        first_pos = idx[allowed][a_first]
        j = np.minimum(np.searchsorted(a_ck, ck), max(len(a_ck) - 1, 0))
        if len(a_ck):
            est |= (a_ck[j] == ck) & (idx > first_pos[j])
        fwd = est | allowed
        created = fwd & ~est
        self.est[ep] = np.union1d(self.est[ep], ck[created])
        reason = np.where(fwd, REASON_FORWARDED,
                          np.where(outcome == DENY, REASON_DENY,
                                   REASON_DEFAULT_DENY)).astype(np.int64)
        np.add.at(self.counters[:, 0], reason, 1)
        msg = np.where(~fwd, MSG_DROP,
                       np.where(est, MSG_TRACE, MSG_POLICY_VERDICT))
        keep = slice(None) if self.every_event else msg != MSG_TRACE
        self._keys.append(event_keys(src[keep], rows[keep, COL_DST_IP3],
                                     rows[keep, COL_SPORT], dport[keep]))
        self._vals.append(event_vals(identity[keep], msg[keep],
                                     reason[keep]))

    def _feed_replies(self, ep: int, rows: np.ndarray) -> None:
        """Egress rows from the endpoint: each must answer an
        established ingress tuple (a conntrack REPLY, which bypasses
        policy and is forwarded as a trace event); any other egress row
        is outside what the reference models."""
        dst = rows[:, COL_DST_IP3]
        pos = np.minimum(np.searchsorted(self.ips_sorted, dst),
                         len(self.ips_sorted) - 1)
        if np.any(self.ips_sorted[pos] != dst):
            raise ValueError("a reply's destination has no /32 entry")
        ingress_key = tuple_keys(dst, rows[:, COL_DPORT],
                                 rows[:, COL_SPORT])
        if not np.all(np.isin(self._ct_key(ingress_key), self.est[ep])):
            raise ValueError("an egress row answers no established "
                             "flow; egress policy is not modelled")
        np.add.at(self.counters[:, 1], REASON_FORWARDED, len(rows))
        if self.every_event:
            self._keys.append(event_keys(rows[:, COL_SRC_IP3], dst,
                                         rows[:, COL_SPORT],
                                         rows[:, COL_DPORT]))
            self._vals.append(event_vals(
                self.ids_sorted[pos], np.full(len(rows), MSG_TRACE),
                np.zeros(len(rows), np.int64)))

    def events(self):
        if not self._keys:
            return np.zeros(0, np.uint64), np.zeros(0, np.uint64)
        return np.concatenate(self._keys), np.concatenate(self._vals)


def multiset_mismatch(keys_a, vals_a, keys_b, vals_b) -> int:
    """Size of the symmetric difference of two multisets of
    (key, value) pairs."""
    def digest(k, v):
        with np.errstate(over="ignore"):
            x = k * np.uint64(0x9E3779B97F4A7C15) ^ v
            x = (x ^ (x >> np.uint64(31))) * np.uint64(0xBF58476D1CE4E5B9)
        return x ^ (x >> np.uint64(29))

    ua, ca = np.unique(digest(keys_a, vals_a), return_counts=True)
    ub, cb = np.unique(digest(keys_b, vals_b), return_counts=True)
    allk = np.union1d(ua, ub)
    na = np.zeros(len(allk), np.int64)
    nb = np.zeros(len(allk), np.int64)
    na[np.searchsorted(allk, ua)] = ca
    nb[np.searchsorted(allk, ub)] = cb
    return int(np.abs(na - nb).sum())
