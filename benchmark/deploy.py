"""Build one deployment (a ``configs/<name>.json`` file) through the
agent's own entry points, and describe it for the plain reference.

The tables are made the way an operator's cluster makes them: one
identity allocated per client pod, a /32 ipcache entry per pod, the
server endpoint, then the policy import.  What the reference needs is
what this module handed to the agent (addresses, the numeric identity
given to each /32 entry, labels, rules), never a table the program
built.
"""

from __future__ import annotations

import ipaddress
import json
import os
from dataclasses import dataclass, field
from typing import List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_config(name: str, tiny: bool = False, path: str = None) -> dict:
    """``configs/<name>.json``; ``tiny`` applies its ``tiny`` block (the
    CPU rehearsal size) over the top-level keys."""
    path = path or os.path.join(HERE, "configs", f"{name}.json")
    with open(path) as f:
        cfg = json.load(f)
    if tiny:
        cfg.update(cfg.get("tiny", {}))
    return cfg


def pod_ip(i: int) -> str:
    return f"10.{(i >> 16) & 0xFF}.{(i >> 8) & 0xFF}.{i & 0xFF}"


@dataclass
class Deployment:
    """What was handed to the agent, plus the daemon itself."""

    daemon: object
    endpoints: List[dict]  # {"id", "ip" (IPv4 as an integer), "labels"}
    client_ips: np.ndarray  # [n] uint32, client i's address
    client_ids: np.ndarray  # [n] uint32, numeric identity of its /32
    client_labels: List[List[str]]
    rules: List[dict] = field(default_factory=list)


def build(cfg: dict) -> Deployment:
    """Construct a ``backend="tpu"`` daemon holding the deployment.
    Every serving knob the deployment does not fix stays at the
    program's default.  The policy is ``policies/<generator>.py``,
    found by the name the configuration gives."""
    import byname
    from cilium_tpu.agent.daemon import Daemon, DaemonConfig
    from cilium_tpu.labels import LabelSet

    kw = {"backend": "tpu", "ct_capacity": int(cfg["ct_capacity"])}
    if cfg.get("ladder"):
        kw["serving_bucket_ladder"] = tuple(cfg["ladder"])
    if cfg.get("queue_depth"):
        kw["serving_queue_depth"] = int(cfg["queue_depth"])
    d = Daemon(DaemonConfig(**kw))
    try:
        n = int(cfg["identities"])
        off = int(cfg["client_ip_offset"])
        ips = np.empty(n, dtype=np.uint32)
        ids = np.empty(n, dtype=np.uint32)
        labels = []
        for i in range(n):
            lab = [t.format(i=i) for t in cfg["client_labels"]]
            ident = d.allocator.allocate(LabelSet.parse(*lab))
            ip = pod_ip(i + off)
            d.upsert_ipcache(ip + "/32", ident.numeric_id)
            ips[i] = int(ipaddress.IPv4Address(ip))
            ids[i] = ident.numeric_id
            labels.append(lab)
        eps = []
        for ep in cfg["endpoints"]:
            e = d.add_endpoint(ep["name"], (ep["ip"],), list(ep["labels"]))
            eps.append({"id": int(e.id),
                        "ip": int(ipaddress.IPv4Address(ep["ip"])),
                        "labels": list(ep["labels"])})
        pol = cfg["policy"]
        rules = byname.load("policies", pol["generator"]).rules(n, pol)
        d.policy_import(rules)
    except BaseException:
        d.shutdown()
        raise
    return Deployment(daemon=d, endpoints=eps, client_ips=ips,
                      client_ids=ids, client_labels=labels, rules=rules)
