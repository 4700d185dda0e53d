"""cilium-tpu CLI (reference: cilium/cmd cobra CLI).

Verbs mirror the reference operator tooling: ``policy import|get|
delete``, ``endpoint list|add|delete``, ``identity list``, ``bpf ct
list``, ``bpf policy get``, ``map list``, ``monitor``, ``status``,
``metrics``, ``flows`` (hubble observe), plus ``daemon run`` to start
an agent serving the API socket.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from ..api.client import DEFAULT_SOCKET, APIClient, APIError


def _client(args) -> APIClient:
    return APIClient(args.socket)


def _print(obj) -> None:
    print(json.dumps(obj, indent=2))


def cmd_status(args) -> int:
    st = _client(args).healthz()
    if args.json:
        _print(st)
        return 0
    print(f"Agent:     {st['node']} v{st['version']} "
          f"(backend={st['backend']} on {st['device-count']}x "
          f"{st['device-kind']} [{st['platform']}], "
          f"up {st['uptime-seconds']}s)")
    print(f"Policy:    revision {st['policy-revision']}, "
          f"{st['identities']} identities, "
          f"{st['ipcache-entries']} ipcache entries")
    eps = st["endpoints"]
    print(f"Endpoints: {eps['total']} ({eps['by-state']})")
    print(f"Datapath:  {st['forwarded']} forwarded, "
          f"{st['dropped']} dropped, {st['flows-seen']} flows seen")
    if "auth" in st:
        a = st["auth"]
        print(f"Auth:      provider={a['provider']} "
              f"granted={a['granted']} failed={a['failed']}")
    if "encryption" in st:
        e = st["encryption"]
        print(f"Encrypt:   wireguard-analogue epoch={e['epoch']} "
              f"peers={len(e['peers'])} "
              f"pubkey={e['public-key'][:16]}...")
    for name, c in st.get("controllers", {}).items():
        ok = "ok" if not c["last-error"] else f"FAILING: {c['last-error']}"
        print(f"Controller {name}: {c['success']} runs, {ok}")
    return 0


def cmd_connectivity(args) -> int:
    """`cilium-tpu connectivity test` (reference: cilium-cli
    connectivity test — BASELINE config 1): self-contained two-pod
    world + the L3/L4/L7/deny/entity/auth scenario matrix through
    the real datapath."""
    from ..testing.connectivity import (format_results,
                                        run_connectivity_tests)
    res = run_connectivity_tests(backend=args.backend)
    if args.json:
        _print([r.__dict__ for r in res])
    else:
        print(format_results(res))
    return 0 if all(r.ok for r in res) else 1


def cmd_encrypt(args) -> int:
    """`cilium-tpu encrypt status` (reference: cilium encrypt
    status)."""
    st = _client(args).healthz()
    enc = st.get("encryption")
    if args.json:
        _print(enc or {"enabled": False})
        return 0
    if not enc:
        print("Encryption: disabled")
        return 0
    print(f"Encryption: wireguard-analogue (X25519 + "
          f"ChaCha20-Poly1305, batch-sealed)")
    print(f"Public key: {enc['public-key']}")
    print(f"Key epoch:  {enc['epoch']}")
    for peer, c in enc["peers"].items():
        print(f"Peer {peer}: sealed={c['sealed']} "
              f"opened={c['opened']} rejected={c['rejected']}")
    return 0


def cmd_policy(args) -> int:
    c = _client(args)
    if args.action == "get":
        _print(c.policy_get())
    elif args.action == "import":
        if not args.file:
            print("usage: cilium-tpu policy import FILE", file=sys.stderr)
            return 1
        with open(args.file) as f:
            rules = json.load(f)
        out = c.policy_put(rules)
        print(f"Revision: {out['revision']}")
    elif args.action == "delete":
        out = c.policy_delete(args.labels.split(","))
        print(f"Revision: {out['revision']}")
    return 0


def cmd_endpoint(args) -> int:
    c = _client(args)
    if args.action == "list":
        eps = c.endpoint_list()
        if args.json:
            _print(eps)
            return 0
        print(f"{'ID':<6}{'STATE':<22}{'IDENTITY':<10}{'IPS':<34}NAME")
        for ep in eps:
            print(f"{ep['id']:<6}{ep['state']:<22}"
                  f"{str(ep['identity']):<10}"
                  f"{','.join(ep['ips']):<34}{ep['name']}")
    elif args.action == "get":
        _print(c.endpoint_get(args.id))
    elif args.action == "add":
        ep = c.endpoint_create(args.name, args.ip, args.label)
        _print(ep)
    elif args.action == "delete":
        _print(c.endpoint_delete(args.id))
    return 0


def cmd_service(args) -> int:
    c = _client(args)
    if args.action == "list":
        svcs = c.service_list()
        if args.json:
            _print(svcs)
            return 0
        print(f"{'NAME':<20}{'FRONTEND':<24}BACKENDS")
        for s in svcs:
            bes = ",".join(f"{b['ip']}:{b['port']}"
                           for b in s["backends"])
            print(f"{s['name']:<20}{s['frontend']:<24}{bes}")
    elif args.action == "upsert":
        if not args.name or not args.frontend:
            print("usage: cilium-tpu service upsert NAME --frontend "
                  "IP:PORT [--backend IP:PORT ...]", file=sys.stderr)
            return 1
        _print(c.service_upsert(args.name, args.frontend,
                                args.backend or []))
    elif args.action == "delete":
        _print(c.service_delete(args.name))
    return 0


def cmd_fqdn(args) -> int:
    entries = _client(args).fqdn_cache()
    if args.json:
        _print(entries)
        return 0
    print(f"{'IP':<40}{'IDENTITY':<12}NAMES")
    for e in entries:
        print(f"{e['ip']:<40}{e['identity']:<12}{','.join(e['names'])}")
    return 0


def cmd_health(args) -> int:
    h = _client(args).cluster_health()
    if args.json:
        _print(h)
        return 0
    print(f"Cluster health (from {h['local']}): "
          f"{h['reachable']} reachable, {h['unreachable']} unreachable")
    for n in h["nodes"]:
        state = (f"reachable {n['latency-ms']}ms" if n["reachable"]
                 else f"UNREACHABLE ({n.get('error', '')})")
        print(f"  {n['name']:<20}{state}")
    return 0


def cmd_cluster(args) -> int:
    """`cilium-tpu cluster status`: the clustermesh serving tier —
    membership, routing table, failover/scale-out history, and the
    cluster-wide no-silent-loss ledger (any member node answers).
    `cilium-tpu cluster scale` adds one replica live (ISSUE 13);
    `cluster scale --down [--node NAME]` retires one (ISSUE 17)."""
    if getattr(args, "action", "status") == "scale":
        down = getattr(args, "down", False)
        rec = _client(args).cluster_scale(
            down=down, node=getattr(args, "node", None))
        if args.json:
            _print(rec)
            return 0
        verb = ("Scaled in: {node} retired" if down
                else "Scaled out: {node} joined").format(
                    node=rec['node'])
        print(f"{verb} "
              f"({rec['nodes-after']} nodes, "
              f"{rec['moved-slots']} slots re-pinned, "
              f"{rec['ct-migrated-entries']} CT entries migrated, "
              f"pause {rec['pause-ms']}ms, survivor recompiles "
              f"{rec['survivor-recompiles']})")
        return 0
    if getattr(args, "action", "status") == "rotate":
        # cluster-wide key-epoch rotation (ISSUE 18): every live
        # encrypted channel re-keys under the grace window, serving
        # uninterrupted
        rec = _client(args).cluster_rotate(
            grace_s=getattr(args, "grace", None))
        if args.json:
            _print(rec)
            return 0
        failed = rec.get("failed") or []
        print(f"Rotated to epoch {rec['epoch']}: "
              f"{len(rec['acked'])} nodes acked in {rec['ms']}ms "
              f"(grace {rec['grace-s']}s)"
              + (f", {len(failed)} FAILED" if failed else ""))
        for f in failed:
            print(f"  {f['node']:<16}{f['error']}")
        return 0
    if getattr(args, "action", "status") == "sysdump":
        # the cluster sysdump archive (ISSUE 14): every worker's
        # flight-recorder bundle + the parent bundle + a manifest
        out = _client(args).cluster_sysdump()
        if args.json:
            _print(out)
            return 0
        man = out.get("manifest") or {}
        print(f"wrote {out.get('path')}")
        for name, ent in sorted((man.get("nodes") or {}).items()):
            state = ("ok" if ent.get("ok")
                     else f"FAILED ({ent.get('error', '?')})")
            print(f"  {name:<16}{state}")
        return 0
    if getattr(args, "action", "status") == "slo":
        # the relay's merged cluster health verdict (ISSUE 19):
        # worst-of over node verdicts, every contribution labeled
        out = _client(args).cluster_slo()
        if args.json:
            _print(out)
            return 0
        un = out.get("unreachable") or []
        print(f"Cluster SLO: {str(out.get('verdict', '?')).upper()} "
              f"({out.get('node-count', 0)} nodes"
              + (f", {len(un)} unreachable" if un else "") + ")")
        for name, e in sorted((out.get("nodes") or {}).items()):
            bad = ", ".join(
                f"{k}={v}"
                for k, v in sorted((e.get("slos") or {}).items())
                if v != "ok")
            extra = e.get("error") or bad
            age = e.get("age-s")
            age_s = "-" if age is None else f"{age:.1f}s"
            print(f"  {name:<16}{e.get('verdict', '?'):<9}"
                  f"age {age_s}"
                  + (f"  {extra}" if extra else ""))
        return 0
    if getattr(args, "action", "status") == "trace":
        # stitched cross-process spans (router-queue -> forward ->
        # worker-admit -> ack) + per-node tracer summaries
        tr = _client(args).cluster_trace()
        if args.json:
            _print(tr)
            return 0
        st = tr.get("stitched")
        if not st:
            print("No stitched spans (set cluster_trace_sample > 0)")
            return 0
        print(f"Stitched spans: {st['committed']} committed, "
              f"{st['dropped']} dropped of {st['sampled']} sampled")
        for hop, h in (st.get("hops-us") or {}).items():
            if h and h.get("count"):
                print(f"  {hop:<28}p50 {_us(h['p50'])} "
                      f"p99 {_us(h['p99'])}")
        for sp in (st.get("spans") or [])[:8]:
            hops = " ".join(f"{k.split('->')[1]}+{_us(v)}"
                            for k, v in sp["hops-us"].items())
            print(f"  #{sp['trace-id']} {sp['node']} "
                  f"rows={sp['rows']} e2e {_us(sp['e2e-us'])}: "
                  f"{hops}")
        return 0
    st = _client(args).cluster_status()
    if args.json:
        _print(st)
        return 0
    c = st["cluster"]
    print(f"Cluster: {c['live']}/{c['nodes']} nodes live "
          f"(mode {c.get('mode', 'thread')}, kvstore {c['kvstore']}, "
          f"failovers {c['failovers']}, "
          f"scale-outs {c.get('scale-outs', 0)})")
    for m in st["membership"]:
        node = st["per-node"].get(m["name"], {})
        mode = node.get("mode") or "-"
        lat = m.get("probe-latency-ms")
        extra = (f"probe {lat}ms" if m["state"] == "live"
                 and lat is not None else
                 m.get("death", {}).get("cause", ""))
        print(f"  {m['name']:<16}{m['state']:<6}mode={mode:<9}{extra}")
    r = c.get("router")
    if r is not None:
        print(f"Router: submitted {r['submitted']}, pending "
              f"{sum(r['pending'])}, overflow {r['router-overflow']}, "
              f"failover-dropped {r['failover-dropped']}, "
              f"crash-dropped {r.get('crash-dropped', 0)}, "
              f"crypto-dropped {r.get('crypto-dropped', 0)}")
        owners = r["slot-owner"]
        counts = {}
        for o in owners:
            counts[o] = counts.get(o, 0) + 1
        share = ", ".join(f"node{o}:{n}"
                          for o, n in sorted(counts.items()))
        print(f"  slots: {len(owners)} ({share})")
        lat = r.get("forward-latency-us") or {}
        if lat.get("count"):
            print(f"  forward latency: p50 {_us(lat['p50'])} "
                  f"p95 {_us(lat['p95'])} p99 {_us(lat['p99'])}")
    led = st["ledger"]
    print(f"Ledger: submitted {led['submitted']} == accounted "
          f"{led['accounted']} -> "
          f"{'EXACT' if led['exact'] else 'OPEN (in flight)'}")
    lf = c.get("last-failover")
    if lf:
        print(f"Last failover: {lf['dead']} -> {lf['peer']} "
              f"(blackout {lf['blackout-ms']}ms, detect "
              f"{lf.get('detect-ms')}ms, CT entries "
              f"{lf['ct-replayed-entries']}, dropped "
              f"{lf['dropped-rows']})")
    ls = c.get("last-scale-out")
    if ls:
        print(f"Last scale-out: {ls['node']} joined "
              f"(pause {ls['pause-ms']}ms, "
              f"{ls['ct-migrated-entries']} CT entries migrated)")
    asc = c.get("autoscale")
    if asc:
        print(f"Autoscale: watermark {asc['high-frac']}, streak "
              f"{asc['streak']}/{asc['ticks']}, triggered "
              f"{asc['triggered']}, max {asc['max-nodes']}")
    cr = c.get("crypto")
    if cr:
        ch = (r or {}).get("crypto") or {}
        print(f"Crypto: epoch {cr['epoch']}, rotations "
              f"{cr['rotations']} (grace {cr['grace-s']}s), sealed "
              f"{ch.get('sealed', 0)}, rejected "
              f"{ch.get('rejected', 0)}, replays "
              f"{ch.get('replays', 0)}")
        lr = c.get("last-rotation")
        if lr:
            print(f"  last rotation: -> epoch {lr['epoch']} "
                  f"({len(lr['acked'])} acked, {lr['ms']}ms)")
    return 0


def _us(v):
    if v is None:
        return "-"
    return f"{v / 1e3:.1f}ms" if v >= 1e3 else f"{v:.0f}µs"


def cmd_config(args) -> int:
    c = _client(args)
    if args.action == "get":
        _print(c.config())
    else:  # set KEY VALUE
        _print(c.config_patch({args.key: args.value}))
    return 0


def cmd_proxy(args) -> int:
    """L7 plane: redirect listeners or the xDS push-surface status."""
    c = _client(args)
    if args.obj == "xds":
        st = c.xds_status()
        if args.json:
            _print(st)
            return 0
        print(f"xDS version {st['version']}, "
              f"{len(st['resources'])} resources")
        for name in st["resources"]:
            print(f"  {name}")
        for nonce, detail in st.get("nacks", ()):
            print(f"  NACK @{nonce}: {detail}")
        return 0
    if args.obj == "stats":
        st = c.proxy_stats()
        if args.json:
            _print(st)
            return 0
        plane = st.get("plane") or {}
        print(f"plane {'ACTIVE' if st.get('plane-active') else 'stopped'}"
              f", {len(st.get('listeners') or ())} listener(s), "
              f"requests {st.get('requests-total', 0)} "
              f"(denied {st.get('requests-denied', 0)})")
        if plane:
            print(f"redirected {plane.get('redirected', 0)}: "
                  f"allowed {plane.get('l7-allowed', 0)} "
                  f"denied {plane.get('l7-denied', 0)} "
                  f"shed {plane.get('l7-shed', 0)} "
                  f"failed {plane.get('l7-failed', 0)} "
                  f"(ledger "
                  f"{'exact' if plane.get('ledger-exact') else 'OPEN'})")
            print(f"workers {plane.get('workers', 0)} "
                  f"restarts {plane.get('worker-restarts', 0)} "
                  f"queue {plane.get('queue-depth', 0)} "
                  f"dns-answers {plane.get('dns-answers', 0)}")
        for name, h in sorted(
                (st.get("parse-latency-by-plugin") or {}).items()):
            print(f"  {name}: p50={h.get('p50')}us "
                  f"p95={h.get('p95')}us p99={h.get('p99')}us "
                  f"n={h.get('count')}")
        return 0
    listeners = c.proxy_listeners()
    if args.json:
        _print(listeners)
        return 0
    for l in listeners:
        rules = {k: v for k, v in l.items()
                 if k.endswith("-rules") and v}
        print(f"port {l['proxy-port']}: {rules or 'no rules'}")
    return 0


def cmd_identity(args) -> int:
    ids = _client(args).identity_list()
    if args.json:
        _print(ids)
        return 0
    print(f"{'ID':<12}LABELS")
    for i in ids:
        print(f"{i['id']:<12}{' '.join(i['labels'])}")
    return 0


def cmd_bpf(args) -> int:
    c = _client(args)
    if args.obj == "ct":
        entries = c.map_get("ct")
        if args.json:
            _print(entries)
            return 0
        for e in entries:
            print(f"{e['proto']} {args_dir(e)} {e['src']}:{e['sport']} "
                  f"-> {e['dst']}:{e['dport']} {e['state']} "
                  f"expires={e['expires']} tx={e['tx_packets']} "
                  f"rx={e['rx_packets']}"
                  + (f" proxy={e['proxy_port']}" if e['proxy_port']
                     else ""))
    elif args.obj == "policy":
        entries = c.map_get(f"policy/{args.id}")
        if args.json:
            _print(entries)
            return 0
        print(f"{'DIR':<9}{'IDENTITY':<10}{'PROTO':<7}{'PORT':<12}"
              f"{'VERDICT':<10}DERIVED-FROM")
        for e in entries:
            print(f"{e['direction']:<9}{e['identity']:<10}"
                  f"{e['proto']:<7}{e['dport']:<12}{e['verdict']:<10}"
                  f"{';'.join(e['derived-from'])}")
    elif args.obj == "ipcache":
        entries = c.map_get("ipcache")
        if args.json:
            _print(entries)
            return 0
        for e in entries:
            print(f"{e['cidr']:<24}identity={e['identity']} "
                  f"source={e['source']}")
    elif args.obj == "lb":
        entries = c.map_get("lb")
        if args.json:
            _print(entries)
            return 0
        for e in entries:
            be = e["backend"] or "(no service)"
            print(f"{e['proto']} {e['src']}:{e['sport']} -> "
                  f"{e['vip']}:{e['dport']} backend={be} "
                  f"expires={e['expires']}")
    elif args.obj == "auth":
        entries = c.map_get("auth")
        if args.json:
            _print(entries)
            return 0
        for e in entries:
            print(f"ep={e['endpoint']} remote-identity="
                  f"{e['remote_identity']} expires={e['expires']}")
    elif args.obj == "nat":
        entries = c.map_get("nat")
        if args.json:
            _print(entries)
            return 0
        for e in entries:
            print(f"{e['proto']} {e['src']}:{e['sport']} -> "
                  f"{e['dst']}:{e['dport']} node-port={e['node_port']} "
                  f"expires={e['expires']}")
    return 0


def args_dir(e) -> str:
    return {"ingress": "in ", "egress": "out"}.get(e.get("dir", ""), "?")


def cmd_egress(args) -> int:
    entries = _client(args).egress_list()
    if args.json:
        _print(entries)
        return 0
    print(f"{'SOURCE':<18}{'DESTINATION':<20}EGRESS-IP")
    for e in entries:
        print(f"{e['source']:<18}{e['destination']:<20}"
              f"{e['egress-ip']}")
    return 0


def cmd_map(args) -> int:
    _print(_client(args).map_list())
    return 0


def cmd_metrics(args) -> int:
    c = _client(args)
    if getattr(args, "cluster", False):
        # the relay's merged exposition: every series node-labelled
        print(c.cluster_metrics(), end="")
        return 0
    print(c.metrics(), end="")
    return 0


def cmd_flows(args) -> int:
    """`cilium-tpu flows [-f]` (hubble observe): recent flows with
    the SHARED filter vocabulary — `--verdict/--identity/--port/
    --protocol/--since` map onto the Observer's vectorized
    FlowFilter, and `top` renders aggregates over the same fields.
    Follow mode tails new flows by uuid."""
    c = _client(args)
    # --since S = "the last S seconds": resolve to the epoch once so
    # a follow session keeps its original left edge
    since = (time.time() - args.since) if args.since else None
    seen = 0
    if getattr(args, "cluster", False):
        # `flows --cluster` (hubble-relay parity): merged
        # time-ordered flows from every node, node_name stamped.
        # The shared filter vocabulary applies CLIENT-side over the
        # merged dicts (the relay buffer is node-merged, not an
        # Observer ring), and -f tails by (node, uuid).
        from ..flow.flow import PROTO_NAMES, VERDICT_NAMES

        want_verdict = (VERDICT_NAMES.get(args.verdict)
                        if args.verdict is not None else None)
        # the merged dicts carry protocol as the l4 key name
        # ("TCP"/"UDP"/...) or {"proto": n} for codes without a name
        want_proto = (PROTO_NAMES.get(args.protocol, args.protocol)
                      if args.protocol is not None else None)

        def keep(fl) -> bool:
            if want_verdict is not None \
                    and fl.get("verdict") != want_verdict:
                return False
            if want_proto is not None:
                l4 = fl.get("l4") or {}
                if want_proto not in l4 \
                        and l4.get("proto") != want_proto:
                    return False
            if args.port is not None:
                l4 = next(iter((fl.get("l4") or {}).values()), {})
                if args.port not in (l4.get("source_port"),
                                     l4.get("destination_port")):
                    return False
            if args.identity is not None:
                idents = {(fl.get("source") or {}).get("identity"),
                          (fl.get("destination") or {})
                          .get("identity")}
                if args.identity not in idents:
                    return False
            if since is not None and fl.get("time", 0) < since:
                return False
            return True

        seen_keys = set()
        try:
            while True:
                flows = [fl for fl in c.cluster_flows(
                    number=args.number, oldest_first=1)
                    if keep(fl)]
                if args.json:
                    _print(flows)
                else:
                    for fl in flows:
                        key = (fl.get("node_name"), fl.get("uuid"))
                        if key in seen_keys:
                            continue
                        seen_keys.add(key)
                        print(f"{fl.get('time', 0):.3f} "
                              f"[{fl.get('node_name', '?')}] "
                              f"{fl.get('Summary', '')}")
                if not args.follow:
                    return 0
                time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0
    try:
        while True:
            flows = c.flows(number=args.number, verdict=args.verdict,
                            port=args.port, protocol=args.protocol,
                            identity=args.identity, since=since)
            if args.json:
                # json mode follows too (one snapshot per tick, like
                # `top --json -f`) instead of silently ignoring -f
                _print(flows)
            else:
                fresh = [f for f in flows if int(f["uuid"]) >= seen]
                for fl in sorted(fresh, key=lambda f: int(f["uuid"])):
                    print(f"{fl['time']:.3f} {fl['Summary']}")
                    seen = max(seen, int(fl["uuid"]) + 1)
            if not args.follow:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


def cmd_top(args) -> int:
    """`cilium-tpu top [-f]`: the flow analytics plane live — top
    talkers (space-saving sketch with its error bound), the
    per-identity verdict matrix over the retained windows, and the
    drop-spike state (GET /flows/aggregate)."""
    c = _client(args)
    if getattr(args, "cluster", False):
        # `top --cluster`: the relay's merged top-K (sketch sums,
        # summed error bounds, per-node scrape health)
        agg = c.cluster_top(top=args.number)
        if args.json:
            _print(agg)
            return 0
        print("Cluster top (merged across nodes; overcount <= "
              f"{agg.get('sketch-error-bound', 0)}):")
        for name, st in (agg.get("nodes") or {}).items():
            mark = "ok" if st.get("ok") else "STALE"
            print(f"  {name:<16}{mark:<7}"
                  f"windows={st.get('windows-closed')} "
                  + ("[IN SPIKE]" if st.get("spike") else ""))
        talkers = agg.get("top-talkers") or []
        if talkers:
            print(f"{'SRC':<24}{'DST':<24}{'PROTO':<7}"
                  f"{'PACKETS':>10}{'BYTES':>13}  NODES")
            for t in talkers[:args.number]:
                print(f"{t['src'] + ':' + str(t['sport']):<24}"
                      f"{t['dst'] + ':' + str(t['dport']):<24}"
                      f"{t['proto']:<7}{t['packets']:>10}"
                      f"{t['bytes']:>13}  "
                      f"{','.join(t.get('nodes', []))}")
        return 0
    try:
        while True:
            agg = c.flows_aggregate(top=args.number)
            if args.json:
                _print(agg)
            elif not agg.get("enabled"):
                print("Flow analytics: disabled "
                      "(flow-agg-enabled=false)")
            else:
                cur = agg.get("current-window") or {}
                spike = agg.get("spike") or {}
                led = agg.get("ledger") or {}
                print(f"Analytics: window {agg.get('window-s')}s x "
                      f"{agg.get('retention')} retained, "
                      f"{agg.get('windows-closed', 0)} closed, "
                      f"{led.get('packets-seen', 0)} packets seen, "
                      f"spikes {spike.get('spikes', 0)}"
                      + (" [IN SPIKE]" if spike.get("in-spike")
                         else ""))
                print(f"Window:    {cur.get('packets', 0)} packets, "
                      f"{cur.get('bytes', 0)} B, "
                      f"{cur.get('drops', 0)} drops "
                      f"(baseline {spike.get('baseline-drops')}, "
                      f"threshold >= {spike.get('min-drops')} or "
                      f"{spike.get('factor')}x)")
                talkers = agg.get("top-talkers") or []
                if talkers:
                    print(f"\nTop talkers (overcount <= "
                          f"{agg.get('sketch-error-bound', 0)}):")
                    print(f"{'SRC':<24}{'DST':<24}{'PROTO':<7}"
                          f"{'PACKETS':>10}{'BYTES':>13}{'ERR':>7}")
                    for t in talkers[:args.number]:
                        print(f"{t['src'] + ':' + str(t['sport']):<24}"
                              f"{t['dst'] + ':' + str(t['dport']):<24}"
                              f"{t['proto']:<7}{t['packets']:>10}"
                              f"{t['bytes']:>13}{t['error']:>7}")
                matrix = agg.get("matrix") or []
                if matrix:
                    print(f"\nVerdict matrix (retained windows):")
                    print(f"{'SRC-ID':<10}{'DST-ID':<10}"
                          f"{'VERDICT':<9}{'REASON':<8}"
                          f"{'PACKETS':>10}{'BYTES':>13}")
                    for m in matrix[:args.number]:
                        print(f"{m['src-identity']:<10}"
                              f"{m['dst-identity']:<10}"
                              f"{m['verdict']:<9}{m['reason']:<8}"
                              f"{m['packets']:>10}{m['bytes']:>13}")
            if not args.follow:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


def cmd_sysdump(args) -> int:
    """`cilium-tpu sysdump [list]`: trigger a manual flight-recorder
    bundle (bypasses the auto rate limit) or list what the incident
    machinery has already captured."""
    c = _client(args)
    if args.action == "list":
        out = c.sysdump(trigger=False)
        if args.json:
            _print(out)
            return 0
        if not out.get("enabled"):
            print("Sysdump: disabled (run the agent with "
                  "--sysdump-dir)")
        for b in out.get("bundles", []):
            print(f"{b['name']}  {b['bytes']} B")
        for i in (out.get("incidents") or [])[-10:]:
            print(f"incident #{i['seq']} {i['kind']} "
                  f"@{i['time']:.3f}")
        return 0
    out = c.sysdump(trigger=True)
    if args.json:
        _print(out)
        return 0 if out.get("written") else 1
    written = out.get("written")
    if not written:
        # enabled but nothing written: another capture held the
        # re-entrancy guard — tell the operator instead of lying
        # "wrote None" with a zero exit
        print("no bundle written (another capture in progress; "
              "retry, or see `sysdump list`)", file=sys.stderr)
        return 1
    print(f"wrote {written}")
    st = out.get("stats") or {}
    print(f"bundles: {len(out.get('bundles', []))} on disk, "
          f"writes {st.get('writes')}, "
          f"incidents {st.get('incidents')}")
    return 0


def cmd_anomaly(args) -> int:
    action = getattr(args, "action", "stats")
    if action == "stats":
        _print(_client(args)._request("GET", "/anomaly"))
        return 0
    # offline verbs: no agent needed (BASELINE eval config #5)
    from ..ml.evaluate import (evaluate_capture, synth_labeled_capture,
                               train_and_evaluate)

    if action == "train":
        result = train_and_evaluate(n_identities=args.identities,
                                    model_out=args.model)
        _print(result)
        return 0
    if not (args.pcap and args.labels):
        print(f"usage: cilium-tpu anomaly {action} --pcap FILE "
              "--labels FILE", file=sys.stderr)
        return 1

    # synth and score MUST agree with train on the world shape —
    # identity rows index the model's embedding table, so a mismatched
    # world silently remaps identities and poisons the AUC
    from ..testing.fixtures import build_world

    world = build_world(n_identities=args.identities, n_rules=16,
                        ct_capacity=1 << 16)
    if action == "synth":
        synth_labeled_capture(args.pcap, args.labels, world,
                              n=args.number)
        print(f"wrote {args.pcap} + {args.labels}")
        return 0
    if action == "score":
        import jax

        from ..ml.model import init_params, load_model

        if args.model:
            model = load_model(args.model)
            if model.embed.shape[0] != world.row_map.capacity:
                print(f"error: model embedding rows "
                      f"({model.embed.shape[0]}) != world identity "
                      f"rows ({world.row_map.capacity}); pass the "
                      "--identities the model was trained with",
                      file=sys.stderr)
                return 1
        else:
            print("note: no --model given; scoring with an untrained "
                  "model", file=sys.stderr)
            model = init_params(jax.random.PRNGKey(0),
                                world.row_map.capacity)
        result = evaluate_capture(model, world, args.pcap, args.labels)
        _print(result)
        return 0
    return 1


# cumulative serving counters the follow mode diffs per interval
# (path into the snapshot dict -> display label)
_SERVING_RATE_KEYS = (
    (("submitted",), "submitted"),
    (("admitted",), "admitted"),
    (("shed",), "shed"),
    (("batches",), "batches"),
    (("dispatch", "dispatches"), "dispatches"),
    (("verdicts",), "verdicts"),
    (("h2d", "bytes"), "h2d-bytes"),
    (("ring", "events"), "ring-events"),
    (("event-plane", "ring-lost"), "ring-lost"),
    (("event-plane", "d2h-bytes"), "d2h-bytes"),
    (("event-plane", "windows-joined"), "windows-joined"),
    (("event-plane", "windows-dropped"), "windows-dropped"),
    (("fault-tolerance", "restarts"), "restarts"),
    (("fault-tolerance", "recovery-dropped"), "recovery-dropped"),
    (("fault-tolerance", "dispatch-timeouts"), "timeouts"),
    # map-pressure counters (datapath/pressure.py): cumulative, so
    # the follow mode renders them as per-interval rates like every
    # other counter here
    (("pressure", "ct", "insert-drops"), "ct-insert-drops"),
    (("pressure", "nat", "failures"), "nat-failures"),
)


def _pluck(st: dict, keys) -> object:
    v = st
    for k in keys:
        if not isinstance(v, dict):
            return None
        v = v.get(k)
    return v


def _counters_reset(cur: dict, prev: dict) -> bool:
    """Any cumulative counter going BACKWARD means the serving
    session restarted between ticks (stop_serving + start_serving
    zeroes them): the diff would render nonsense negative rates, so
    the follow loop resyncs with a full block instead.  The reset
    DEFINITION lives in ``obs.history`` — the one convention shared
    with the SeriesHistory ring's splice — this wrapper only plucks
    the serving rate keys."""
    from ..obs.history import counters_reset

    return counters_reset(
        (_pluck(cur, keys), _pluck(prev, keys))
        for keys, _label in _SERVING_RATE_KEYS)


def _print_serving_interval(cur: dict, prev: dict,
                            dt: float) -> None:
    """Follow-mode rendering: DIFF the cumulative counters against
    the previous sample so each tick reads as a rate, not a growing
    total (totals made chaos runs unreadable — a restart burst looks
    identical to steady state when you only see lifetime sums)."""
    parts = []
    for keys, label in _SERVING_RATE_KEYS:
        a, b = _pluck(cur, keys), _pluck(prev, keys)
        if a is None or b is None or not isinstance(a, (int, float)):
            continue
        delta = a - b
        if delta == 0 and label not in ("submitted", "verdicts"):
            continue  # quiet counters stay off the line
        parts.append(f"{label} +{delta:g} ({delta / dt:,.0f}/s)")
    print(f"[{dt:.1f}s] " + ", ".join(parts))
    q = cur.get("queue-pending", 0)
    lat = cur.get("latency-us") or {}
    mode = cur.get("mode")
    tail = (f"     queue {q}/{cur.get('queue-depth', 0)}, "
            f"p50={lat.get('p50')}us p99={lat.get('p99')}us")
    if mode:
        tail += f", mode={mode}"
    print(tail)


def cmd_serving(args) -> int:
    """`cilium-tpu serving stats [--follow]`: the serving front-end's
    live telemetry (queue depth/wait, pad efficiency, batches/sec,
    verdicts/sec, shed counters, p50/p95/p99 latency).  Follow mode
    diffs the cumulative counters per interval."""
    c = _client(args)
    prev = None
    prev_t = None
    try:
        while True:
            st = c.serving_stats()
            now = time.monotonic()
            if args.json:
                _print(st)
            elif (prev is not None and st.get("active")
                    and prev.get("active")
                    and not _counters_reset(st, prev)):
                _print_serving_interval(st, prev, max(now - prev_t,
                                                      1e-9))
            elif not st.get("active"):
                print("Serving: inactive (start_serving has not run)")
            else:
                ring = st.get("ring", {})
                print(f"Serving:   up {st.get('uptime-seconds', 0)}s, "
                      f"{st.get('batches', 0)} batches, "
                      f"{st.get('batches-per-sec', 0)}/s")
                print(f"Verdicts:  {st.get('verdicts', 0)} "
                      f"({st.get('verdicts-per-sec', 0)}/s), "
                      f"pad-efficiency {st.get('pad-efficiency')}")
                print(f"Queue:     {st.get('queue-pending', 0)}/"
                      f"{st.get('queue-depth', 0)} pending, "
                      f"admitted {st.get('admitted', 0)}, "
                      f"shed {st.get('shed', 0)} "
                      f"({st.get('shed-events', 0)} as drop events)")
                print(f"Shapes:    {st.get('batch-shapes', {})}")
                dp = st.get("dispatch") or {}
                if dp.get("superbatches"):
                    fill = dp.get("superbatch-fill")
                    print(f"Dispatch:  {dp.get('dispatches', 0)} "
                          f"dispatches, "
                          f"{dp.get('batches-per-dispatch')} "
                          f"batches/dispatch "
                          f"({dp.get('superbatches', 0)} superbatches"
                          f" {dp.get('superbatch-shapes', {})}, "
                          f"fill {'-' if fill is None else fill})")
                h2d = st.get("h2d") or {}
                if h2d.get("packed-batches") or h2d.get("wide-batches"):
                    print(f"H2D:       {h2d.get('bytes-per-packet')} "
                          f"B/packet "
                          f"({h2d.get('packed-batches', 0)} packed / "
                          f"{h2d.get('wide-batches', 0)} wide batches)")
                if st.get("shards"):
                    print(f"Shards:    {st['shards']} chips, "
                          f"route-overflow "
                          f"{st.get('route-overflow', 0)}")
                ft = st.get("fault-tolerance") or {}
                if ft.get("supervised"):
                    lad = st.get("ladder") or {}
                    mode = st.get("mode", "?")
                    flag = (" DEGRADED" if lad.get("degraded")
                            else "")
                    print(f"Fault-tol: mode={mode}{flag}, restarts "
                          f"{ft.get('restarts', 0)}/"
                          f"{ft.get('restart-budget', 0)}, "
                          f"recovery-dropped "
                          f"{ft.get('recovery-dropped', 0)} "
                          f"({ft.get('dispatch-timeouts', 0)} "
                          f"deadline hits), demotions "
                          f"{lad.get('demotions', 0)}")
                snap = st.get("ct-snapshot")
                if snap:
                    print(f"CT-snap:   {snap.get('entries', 0)} "
                          f"entries, age "
                          f"{snap.get('age-seconds', 0)}s "
                          f"({snap.get('trigger')}, "
                          f"mode {snap.get('mode')})")
                pr = st.get("pressure")
                if pr and pr.get("ct"):
                    ct = pr["ct"]
                    nat = pr.get("nat") or {}
                    occ = ct.get("occupancy")
                    flag = (" ACCELERATED (gc "
                            f"{pr.get('gc-pressure-interval-s')}s)"
                            if pr.get("accelerated") else "")
                    print(f"Pressure:  {pr.get('state', '?')}{flag}, "
                          f"ct {ct.get('occupied', 0)}/"
                          f"{ct.get('capacity', 0)} "
                          f"({'-' if occ is None else occ}), "
                          f"insert-drops {ct.get('insert-drops', 0)}"
                          f", nat-failures {nat.get('failures', 0)}, "
                          f"episodes {pr.get('episodes', 0)}")
                tb = st.get("tables")
                if tb:
                    stall = tb.get("swap-stall-us") or {}
                    vis = tb.get("update-visible-us") or {}
                    print(f"Tables:    gen {tb.get('generation', 0)}, "
                          f"{tb.get('swaps', 0)} swaps "
                          f"({tb.get('delta-attaches', 0)} delta / "
                          f"{tb.get('full-attaches', 0)} full / "
                          f"{tb.get('patches', 0)} patches), "
                          f"stall p99={_us(stall.get('p99'))} "
                          f"visible p99={_us(vis.get('p99'))} "
                          f"last {_us(tb.get('last-swap-us'))}")
                for name, key in (("Queue-wait", "queue-wait-us"),
                                  ("Latency", "latency-us")):
                    h = st.get(key) or {}
                    print(f"{name}: p50={h.get('p50')}us "
                          f"p95={h.get('p95')}us p99={h.get('p99')}us "
                          f"max={h.get('max')}us n={h.get('count')}")
                print(f"Ring:      {ring.get('windows', 0)} windows, "
                      f"{ring.get('events', 0)} events, "
                      f"{ring.get('lost', 0)} lost")
                ev = st.get("event-plane") or {}
                if ev:
                    lag = ev.get("join-lag-us") or {}
                    print(f"Event:     {ev.get('windows-joined', 0)} "
                          f"windows joined / "
                          f"{ev.get('windows-dropped', 0)} dropped "
                          f"({ev.get('queue-overflows', 0)} queue "
                          f"overflows), {ev.get('windows-pending', 0)}"
                          f"/{ev.get('queue-depth', 0)} pending, "
                          f"ring-lost {ev.get('ring-lost', 0)}")
                    bpe = ev.get("d2h-bytes-per-event")
                    print(f"           d2h "
                          f"{ev.get('d2h-bytes', 0)} B "
                          f"({'-' if bpe is None else bpe} B/event)"
                          f", join-lag p50={_us(lag.get('p50'))} "
                          f"p99={_us(lag.get('p99'))}, restarts "
                          f"{ev.get('worker-restarts', 0)}"
                          + (f" TERMINAL: {ev['error']}"
                             if ev.get("error") else ""))
            prev, prev_t = st, now
            if not args.follow:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


def _print_profile_state(tr: dict) -> None:
    """The jax.profiler capture-window status line — a capture can
    be armed with tracing off, so both cmd_trace branches print it."""
    prof = tr.get("profile")
    if prof:
        print(f"Profile:   {prof['state']} "
              f"({prof['batches']}/{prof['window']} "
              f"batches) -> {prof['dir']}")


def cmd_trace(args) -> int:
    """`cilium-tpu trace [-f]`: the sampled span plane — per-stage
    latency breakdown across the serving pipeline (admission ->
    dequeue -> staging -> dispatch -> device -> verdict join) plus
    the slowest-trace table and the compile-event log."""
    c = _client(args)
    try:
        while True:
            tr = c.debug_traces(limit=args.number)
            if args.json:
                _print(tr)
            elif not tr.get("enabled"):
                print("Tracing: off (start_serving(ingress=True) "
                      "with serving_trace_sample=N, or "
                      "span_sample=N)")
                comp = tr.get("compile")
                if comp:
                    print(f"Compiles:  {comp['compiles']} "
                          f"({comp['executables']} executables, "
                          f"{comp['violations']} violations)")
                _print_profile_state(tr)
            else:
                print(f"Tracing:   1-in-{tr['sample']} sampled; "
                      f"{tr['completed']} complete, "
                      f"{tr['started']} started, "
                      f"{tr['dropped']} dropped"
                      + (f", mode={tr['mode']}" if tr.get("mode")
                         else ""))
                print(f"{'STAGE':<20}{'P50us':>10}{'P95us':>10}"
                      f"{'P99us':>10}{'MAXus':>10}{'N':>8}")
                stages = tr.get("stages-us") or {}
                for name, h in stages.items():
                    print(f"{name:<20}"
                          f"{_us(h.get('p50')):>10}"
                          f"{_us(h.get('p95')):>10}"
                          f"{_us(h.get('p99')):>10}"
                          f"{_us(h.get('max')):>10}"
                          f"{h.get('count', 0):>8}")
                e2e = tr.get("e2e-us") or {}
                print(f"{'end-to-end':<20}"
                      f"{_us(e2e.get('p50')):>10}"
                      f"{_us(e2e.get('p95')):>10}"
                      f"{_us(e2e.get('p99')):>10}"
                      f"{_us(e2e.get('max')):>10}"
                      f"{e2e.get('count', 0):>8}")
                slow = tr.get("slowest") or []
                if slow:
                    print(f"\nSlowest traces:")
                    print(f"{'SEQ':<10}{'E2Eus':>10}{'BUCKET':>8}"
                          f"{'MODE':>16}{'SHARD':>7}{'DEMOTED':>9}"
                          f"  SLOWEST-STAGE")
                    for t in slow[:args.number]:
                        st = t.get("stages-us") or {}
                        worst = max(st, key=st.get) if st else ""
                        tail = (f"  {worst} ({_us(st.get(worst))}us)"
                                if worst else "")
                        shard = t.get("shard", -1)
                        print(f"{t['seq']:<10}"
                              f"{_us(t.get('e2e-us')):>10}"
                              f"{t.get('bucket', 0):>8}"
                              f"{t.get('mode', ''):>16}"
                              f"{shard if shard >= 0 else '':>7}"
                              f"{'yes' if t.get('demoted') else '':>9}"
                              + tail)
                comp = tr.get("compile") or {}
                if comp:
                    print(f"\nCompiles:  {comp['compiles']} "
                          f"({comp['executables']} executables, "
                          f"{comp['violations']} violations)")
                    for ev in (comp.get("events") or [])[-5:]:
                        print(f"  {ev['mode']:<16}"
                              f"shape={tuple(ev['shape'])} "
                              f"{ev['compile-ms']}ms"
                              + (" DUPLICATE" if ev["duplicate"]
                                 else ""))
                _print_profile_state(tr)
            if not args.follow:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


def _us(v) -> str:
    return "-" if v is None else f"{v:,.0f}"


def _print_slo(st: dict) -> None:
    en = "" if st.get("enabled") else " (sampler disabled)"
    print(f"Verdict:   {str(st.get('verdict', '?')).upper()}{en} — "
          f"{st.get('ticks', 0)} ticks, windows "
          f"{st.get('fast-window-s')}s/{st.get('slow-window-s')}s, "
          f"page>={st.get('page-burn')}x warn>={st.get('warn-burn')}x"
          f", resyncs {st.get('resyncs', 0)}")
    slos = st.get("slos") or {}
    if not slos:
        print("  (no evaluations yet — first tick pending)")
    else:
        print(f"  {'SLO':<26}{'STATE':<9}{'BUDGET':>8}"
              f"{'FAST-BURN':>11}{'SLOW-BURN':>11}")
        for name, ev in sorted(slos.items()):
            bud = ev.get("budget-remaining")
            fb = ev.get("fast-burn")
            sb = ev.get("slow-burn")
            bud_s = "-" if bud is None else f"{bud:.1%}"
            fb_s = "-" if fb is None else f"{fb:.2f}x"
            sb_s = "-" if sb is None else f"{sb:.2f}x"
            print(f"  {name:<26}{ev.get('state', '?'):<9}"
                  f"{bud_s:>8}{fb_s:>11}{sb_s:>11}")
    for name, ep in sorted((st.get("active") or {}).items()):
        print(f"  BURNING {name}: peak {ep.get('peak-burn')}x, "
              f"calm {ep.get('calm', 0)}/{st.get('clear-ticks')} "
              f"(since {ep.get('started-at')})")
    for e in (st.get("episodes") or [])[-3:]:
        print(f"  recovered {e.get('slo')}: "
              f"{e.get('duration-s')}s burn episode, "
              f"peak {e.get('peak-burn')}x")


def cmd_slo(args) -> int:
    """`cilium-tpu slo [-f]`: the SLO plane (ISSUE 19) — per-SLO
    multi-window burn rates, budget remaining, burn-episode state,
    and the node verdict.  Follow mode re-renders per interval."""
    c = _client(args)
    try:
        while True:
            st = c.slo()
            if args.json:
                _print(st)
            else:
                _print_slo(st)
            if not args.follow:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


def cmd_history(args) -> int:
    """`cilium-tpu history [SERIES...]`: the in-process metrics
    history ring (ISSUE 19) — recent fast-tier samples per series,
    newest last; histograms render their cumulative event count.
    No Prometheus required."""
    c = _client(args)
    h = c.metrics_history(series=args.series or None,
                          since=args.since or 0.0)
    if args.json:
        _print(h)
        return 0
    fast = h.get("fast") or []
    print(f"History:   {h.get('samples', 0)} samples, "
          f"{h.get('resyncs', 0)} resyncs; fast {len(fast)}"
          f"x{h.get('interval-s')}s, slow {len(h.get('slow') or [])}"
          f" (1-in-{h.get('slow-every')})")
    recs = fast[-args.number:]
    for name in h.get("series") or []:
        vals = []
        for r in recs:
            v = (r.get("v") or {}).get(name)
            if isinstance(v, dict):
                v = v.get("count")
            vals.append("-" if v is None else f"{v:g}")
        print(f"  {name:<44}{' '.join(vals)}")
    return 0


def cmd_monitor(args) -> int:
    """Tail the flow stream (reference: `cilium monitor`)."""
    c = _client(args)
    seen = 0
    try:
        while True:
            flows = c.flows(number=500)
            fresh = [f for f in flows if int(f["uuid"]) >= seen]
            for fl in sorted(fresh, key=lambda f: int(f["uuid"])):
                print(f"{fl['time']:.3f} [{fl['event_type']['type']}] "
                      f"{fl['Summary']}")
                seen = max(seen, int(fl["uuid"]) + 1)
            if not args.follow:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


def cmd_daemon(args) -> int:
    import os

    from ..agent.config import load_config
    from ..agent.daemon import Daemon
    from ..api.server import APIServer
    from ..infra.jaxcache import enable_compile_cache

    # resolution order (agent/config.py): defaults < --config-dir
    # files < CILIUM_TPU_* env < explicit CLI flags
    overrides = {k: v for k, v in {
        "node_name": args.node_name,
        "backend": args.backend,
        "state_dir": args.state_dir,
        "export_path": args.export,
        "anomaly_model_path": args.anomaly_model,
        "serving_queue_depth": args.serving_queue_depth,
        "serving_bucket_ladder": args.serving_bucket_ladder,
        "serving_max_wait_us": args.serving_max_wait_us,
        "serving_overflow_policy": args.serving_overflow_policy,
        "serving_packed_ingest": args.serving_packed_ingest,
        "serving_dispatch_deadline_ms":
            args.serving_dispatch_deadline_ms,
        "serving_restart_budget": args.serving_restart_budget,
        "ct_snapshot_interval": args.ct_snapshot_interval,
        "fault_injection": args.fault_injection,
        "serving_trace_sample": args.serving_trace_sample,
        "profile_dir": args.profile_dir,
        "profile_batches": args.profile_batches,
        "sysdump_dir": args.sysdump_dir,
        "flow_agg_enabled": args.flow_agg,
    }.items() if v is not None}
    cfg = load_config(config_dir=args.config_dir, **overrides)
    enable_compile_cache()
    d = Daemon(cfg)
    if args.state_dir and d.restore(args.state_dir):
        print(f"restored state from {args.state_dir}")
    d.start()
    sock_dir = os.path.dirname(args.socket)
    if sock_dir:
        os.makedirs(sock_dir, exist_ok=True)
    server = APIServer(d, args.socket)
    server.start()
    print(f"cilium-tpu agent up — API on {args.socket}")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        print("shutting down")
        server.stop()
        d.shutdown()
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="cilium-tpu",
        description="TPU-native network policy + flow analytics CLI",
    )
    parser.add_argument("--socket", default=DEFAULT_SOCKET,
                        help="agent API socket path")
    parser.add_argument("--json", action="store_true",
                        help="raw JSON output")
    sub = parser.add_subparsers(dest="cmd")

    sub.add_parser("version", help="print version")
    sub.add_parser("status", help="agent status")

    p = sub.add_parser("policy", help="policy get|import|delete")
    p.add_argument("action", choices=["get", "import", "delete"])
    p.add_argument("file", nargs="?", help="rules JSON (import)")
    p.add_argument("--labels", default="", help="labels (delete)")

    p = sub.add_parser("endpoint", help="endpoint list|get|add|delete")
    p.add_argument("action", choices=["list", "get", "add", "delete"])
    p.add_argument("id", nargs="?", type=int)
    p.add_argument("--name", default="ep")
    p.add_argument("--ip", action="append", default=[])
    p.add_argument("--label", action="append", default=[])

    sub.add_parser("identity", help="identity list")

    p = sub.add_parser("service", help="service list|upsert|delete")
    p.add_argument("action", choices=["list", "upsert", "delete"])
    p.add_argument("name", nargs="?", default="")
    p.add_argument("--frontend", help="VIP ip:port")
    p.add_argument("--backend", action="append", help="backend ip:port")

    p = sub.add_parser("fqdn", help="fqdn cache list")
    p.add_argument("action", nargs="?", default="cache",
                   choices=["cache"])

    sub.add_parser("health", help="cluster health (probe mesh)")

    p = sub.add_parser("cluster",
                       help="clustermesh serving tier: status "
                            "(membership, router, failovers, ledger)"
                            " | scale (live add_node; --down retires"
                            " one) | sysdump (all-node archive) | "
                            "trace (stitched cross-process spans) | "
                            "rotate (key-epoch rotation, live) | "
                            "slo (merged node-labeled health "
                            "verdict)")
    p.add_argument("action", nargs="?", default="status",
                   choices=["status", "scale", "sysdump", "trace",
                            "rotate", "slo"])
    p.add_argument("--down", action="store_true",
                   help="scale IN: retire one replica (drain its "
                        "send window, re-pin slots, migrate CT)")
    p.add_argument("--node",
                   help="scale --down victim (default: the "
                        "highest-index live node)")
    p.add_argument("--grace", type=float,
                   help="rotate: seconds old-epoch frames stay "
                        "openable (default cluster_epoch_grace_s)")

    p = sub.add_parser("config", help="config get | set KEY VALUE")
    p.add_argument("action", nargs="?", default="get",
                   choices=["get", "set"])
    p.add_argument("key", nargs="?")
    p.add_argument("value", nargs="?")

    p = sub.add_parser("proxy",
                       help="proxy listeners | proxy stats (L7 plane "
                            "ledger) | proxy xds (push status)")
    p.add_argument("obj", nargs="?", default="listeners",
                   choices=["listeners", "stats", "xds"])

    p = sub.add_parser("bpf", help="bpf ct list | bpf policy get ID | "
                                   "bpf ipcache list | bpf nat list | "
                                   "bpf lb list | bpf auth list")
    p.add_argument("obj", choices=["ct", "policy", "ipcache", "nat",
                                   "lb", "auth"])
    p.add_argument("action", nargs="?", default="list")
    p.add_argument("id", nargs="?", type=int, default=0)

    p = sub.add_parser("connectivity",
                       help="connectivity test (self-contained)")
    p.add_argument("action", nargs="?", default="test",
                   choices=["test"])
    p.add_argument("--backend", default="interpreter",
                   choices=["interpreter", "tpu"])

    p = sub.add_parser("encrypt", help="encrypt status")
    p.add_argument("action", nargs="?", default="status",
                   choices=["status"])

    sub.add_parser("egress", help="egress-gateway rules (expanded)")
    sub.add_parser("map", help="list datapath maps")
    p = sub.add_parser("metrics", help="prometheus metrics")
    p.add_argument("--cluster", action="store_true",
                   help="the relay's merged cluster exposition "
                        "(every series node-labelled)")

    p = sub.add_parser("flows", help="recent flows (hubble observe); "
                                     "-f tails, filters share the "
                                     "`top` vocabulary")
    p.add_argument("--cluster", action="store_true",
                   help="merged time-ordered flows from every "
                        "cluster node (node_name stamped)")
    p.add_argument("--number", type=int, default=20)
    p.add_argument("--verdict", type=int)
    p.add_argument("--port", type=int)
    p.add_argument("--protocol", type=int)
    p.add_argument("--identity", type=int,
                   help="the flow's remote security identity "
                        "(numeric)")
    p.add_argument("--since", type=float,
                   help="only flows from the last SECONDS")
    p.add_argument("--follow", "-f", action="store_true")
    p.add_argument("--interval", type=float, default=1.0)

    p = sub.add_parser("top",
                       help="live top talkers + per-identity verdict "
                            "matrix + drop-spike state (the flow "
                            "analytics plane)")
    p.add_argument("--cluster", action="store_true",
                   help="top-K merged across every cluster node "
                        "(sketch sums + summed error bounds)")
    p.add_argument("--follow", "-f", action="store_true")
    p.add_argument("--interval", type=float, default=1.0)
    p.add_argument("--number", type=int, default=10,
                   help="rows per table")

    p = sub.add_parser("sysdump",
                       help="trigger a flight-recorder bundle | "
                            "sysdump list")
    p.add_argument("action", nargs="?", default="capture",
                   choices=["capture", "list"])

    p = sub.add_parser("monitor", help="tail the event stream")
    p.add_argument("--follow", "-f", action="store_true")
    p.add_argument("--interval", type=float, default=1.0)

    p = sub.add_parser("serving",
                       help="serving front-end stats (queue, batches, "
                            "sheds, latency percentiles); follow "
                            "mode diffs counters per interval")
    p.add_argument("action", nargs="?", default="stats",
                   choices=["stats"])
    p.add_argument("--follow", "-f", action="store_true")
    p.add_argument("--interval", type=float, default=1.0)

    p = sub.add_parser("trace",
                       help="sampled per-packet traces: per-stage "
                            "latency breakdown, slowest-trace table, "
                            "compile-event log")
    p.add_argument("--follow", "-f", action="store_true")
    p.add_argument("--interval", type=float, default=1.0)
    p.add_argument("--number", type=int, default=10,
                   help="traces to show in the slowest table")

    p = sub.add_parser("slo",
                       help="the SLO plane: per-SLO multi-window "
                            "burn rates, budget remaining, burn "
                            "episodes, node verdict")
    p.add_argument("--follow", "-f", action="store_true")
    p.add_argument("--interval", type=float, default=1.0)

    p = sub.add_parser("history",
                       help="in-process metrics history ring: "
                            "recent samples per declared series "
                            "(10s fast tier + 5min slow tier)")
    p.add_argument("series", nargs="*",
                   help="series names (default: every declared "
                        "history series)")
    p.add_argument("--since", type=float, default=0.0,
                   help="only samples from the last SECONDS")
    p.add_argument("--number", type=int, default=12,
                   help="fast-tier samples to render per series")

    p = sub.add_parser("anomaly", help="anomaly stats | train | synth "
                                       "| score (pcap evaluation)")
    p.add_argument("action", nargs="?", default="stats",
                   choices=["stats", "train", "synth", "score"])
    p.add_argument("--pcap", help="capture file")
    p.add_argument("--labels", help="label sidecar (.npz or CIC .csv)")
    p.add_argument("--model", help="AnomalyModel .npz path")
    p.add_argument("--number", type=int, default=65536,
                   help="packets for synth")
    p.add_argument("--identities", type=int, default=1024,
                   help="world size; must match across train/synth/"
                        "score (identity rows index the embedding)")

    p = sub.add_parser("daemon", help="run the agent")
    p.add_argument("action", choices=["run"])
    p.add_argument("--config-dir",
                   help="one-file-per-key config dir (the mounted "
                        "cilium-config ConfigMap layout); CLI flags "
                        "override it, CILIUM_TPU_* env between")
    p.add_argument("--backend", default=None,
                   choices=["tpu", "interpreter"])
    p.add_argument("--node-name", default=None)
    p.add_argument("--state-dir")
    p.add_argument("--export", help="flow export JSONL path")
    p.add_argument("--anomaly-model", help="trained AnomalyModel .npz")
    p.add_argument("--serving-queue-depth", type=int, default=None,
                   help="serving admission queue capacity in packets "
                        "(default 65536); overflow sheds by "
                        "--serving-overflow-policy and is counted as "
                        "monitor drop events")
    p.add_argument("--serving-bucket-ladder", default=None,
                   help="comma-separated power-of-two batch buckets, "
                        "ascending (default 1024,4096,16384,65536); "
                        "each distinct bucket is one JIT-compiled "
                        "shape, so the ladder bounds recompiles")
    p.add_argument("--serving-max-wait-us", type=float, default=None,
                   help="max microseconds a queued packet waits before "
                        "a partial bucket flushes (default 2000); "
                        "bounds tail latency at low load")
    p.add_argument("--serving-overflow-policy", default=None,
                   choices=["drop-tail", "drop-oldest"],
                   help="admission shed policy when the queue is full "
                        "(default drop-tail: arriving overflow sheds; "
                        "drop-oldest evicts stale queued rows)")
    p.add_argument("--serving-packed-ingest", default=None,
                   choices=["true", "false"],
                   help="ship eligible IPv4 single-stream batches as "
                        "the packed 16 B/packet h2d wire format (4x "
                        "fewer bytes than wide rows; IPv6/mixed "
                        "streams fall back to wide per batch); "
                        "'false' overrides a config-dir/env true")
    p.add_argument("--serving-dispatch-deadline-ms", type=float,
                   default=None,
                   help="per-batch dispatch deadline in ms (default "
                        "1000): a dispatch exceeding it is declared "
                        "hung, its rows counted as DISPATCH_TIMEOUT "
                        "drops, and the drain loop restarted; 0 "
                        "disables hang detection")
    p.add_argument("--serving-restart-budget", type=int, default=None,
                   help="drain-loop restarts the serving watchdog "
                        "may spend before going terminal (default "
                        "8; 0 disables supervision)")
    p.add_argument("--ct-snapshot-interval", type=float, default=None,
                   help="periodic CT snapshot cadence in seconds "
                        "(default 0 = only on demotion/checkpoint); "
                        "recovery restores established flows from "
                        "the last snapshot when the live CT is "
                        "unreadable")
    p.add_argument("--fault-injection", default=None,
                   help="deterministic fault-injection spec "
                        "(infra/faults.py), e.g. "
                        "'serving.dispatch=1x1~0.3'; chaos testing "
                        "only")
    p.add_argument("--serving-trace-sample", type=int, default=None,
                   help="sample 1-in-N admitted packets with a "
                        "per-packet trace span (six-stage latency "
                        "breakdown via GET /debug/traces and "
                        "`cilium-tpu trace`); default 0 = off = "
                        "zero overhead")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of the first "
                        "--profile-batches serving dispatches into "
                        "this directory (TensorBoard/Perfetto "
                        "viewable), then stop")
    p.add_argument("--profile-batches", type=int, default=None,
                   help="profile capture window length in batches "
                        "(default 16)")
    p.add_argument("--sysdump-dir", default=None,
                   help="incident flight-recorder bundle directory: "
                        "drop-spike / watchdog-restart / "
                        "ladder-demotion / terminal-event-worker / "
                        "manual incidents each capture a bounded "
                        "JSON sysdump here (retention-capped); "
                        "unset = incidents recorded, no bundles")
    p.add_argument("--flow-agg", default=None,
                   choices=["true", "false"],
                   help="flow analytics plane (windowed per-identity "
                        "aggregation, top-K talkers, drop-spike "
                        "detection; runs off the dispatch path on "
                        "the event-join worker; default true)")

    args = parser.parse_args(argv)
    if args.cmd == "version":
        from .. import __version__

        print(f"cilium-tpu {__version__}")
        return 0
    try:
        handler = {
            "status": cmd_status, "policy": cmd_policy,
            "endpoint": cmd_endpoint, "identity": cmd_identity,
            "bpf": cmd_bpf, "map": cmd_map, "metrics": cmd_metrics,
            "flows": cmd_flows, "monitor": cmd_monitor,
            "top": cmd_top, "sysdump": cmd_sysdump,
            "serving": cmd_serving, "trace": cmd_trace,
            "slo": cmd_slo, "history": cmd_history,
            "anomaly": cmd_anomaly, "daemon": cmd_daemon,
            "service": cmd_service, "fqdn": cmd_fqdn,
            "health": cmd_health, "cluster": cmd_cluster,
            "config": cmd_config,
            "proxy": cmd_proxy,
            "egress": cmd_egress,
            "encrypt": cmd_encrypt,
            "connectivity": cmd_connectivity,
        }.get(args.cmd)
        if handler is None:
            parser.print_help()
            return 1
        return handler(args)
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    except ConnectionRefusedError:
        print(f"error: agent not reachable on {args.socket} "
              "(start one: cilium-tpu daemon run)", file=sys.stderr)
        return 1
    except APIError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
