"""Parent-side handles for process-per-node cluster serving: spawn,
channel brokerage, and the ``ProcessNode`` replica interface.

``ProcessNode`` presents the same duck-typed surface as the
in-process ``ClusterNode`` (``.name`` / ``.alive`` / ``.submit`` /
``.probe`` / ``.crash`` plus the node-interface methods the failover
and scale-out orchestrators call), so ``ClusterRouter`` /
``ClusterMembership`` / ``FailoverOrchestrator`` run UNCHANGED over
real worker processes — the composition proof the kvstore transport
already made for the identity plane.

Crash accounting (the piece SIGKILL makes hard): every data-channel
frame is acked with the worker's running packet ledger, and the
parent retains the newest ack.  A SIGKILLed worker's last ack is its
final word: ``final`` snapshots the acked counters, and the delta
between the acked ``submitted`` and the acked accounted counters
(verdicts + shed + recovery_dropped) — the rows the worker had
admitted but not yet resolved — is handed to
``router.account_crash_loss`` as ``crash_dropped``.  Rows in frames
the worker never acked are still the forwarder's (requeued on the
send/ack error, migrated or counted by failover), so::

    submitted == per-node accounted + router_overflow
                 + failover_dropped + crash_dropped

stays EXACT over a corpse.  (Between the last ack and the kill the
worker may have resolved a few more rows; the ledger attributes them
to ``crash_dropped`` instead of ``verdicts`` — loss is never
under-counted, which is the contract.)

PIPELINED MODE (ISSUE 17): with ``cluster_forward_window > 1`` the
router enables a SEND WINDOW on each process node — ``submit``
returns after the sequenced frame is on the wire (blocking only
while the window is full: credit backpressure), and a dedicated
ACK-READER thread retires in-flight frames as the worker's
CUMULATIVE acks arrive, returning credit to the forwarder through
the router's ``on_ack`` callback.  The crash contract is unchanged
because it never depended on synchrony: the last cumulative ack's
ledger covers exactly the frames the window has retired, and every
sent-but-unacked frame is retained WITH ITS ROWS in the window —
on channel death the ack reader hands them back to the router
(``on_broken``), where they are requeued for the failover peer or
counted, so the identity above closes at ANY kill point inside an
open window.  Window=1 keeps the PR 13 sync path byte-identical.
"""

from __future__ import annotations

import collections
import dataclasses
import secrets
import socket
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..encryption import DecryptError
from ..infra.faults import InjectedFault
from ..serving import ServingError
from .nodehost import OP_TIMEOUTS
from .transport import (SendWindow, encode_rows, is_crypto_reject,
                        recv_frame, recv_json_frame, rows_from_b64,
                        rows_to_b64, send_frame, send_json_frame,
                        shutdown_close, unpack_ack_ex,
                        unpack_crypto_reject, unpack_cum_ack)

__all__ = ["ProcessNode", "ProcessNodeSpawner", "spawn_available",
           "CRYPTO_DESYNC_THRESHOLD"]

# ENCRYPTED MODE (ISSUE 18): consecutive parent-side ack/NACK open
# failures in the KEY-MISMATCH class before the channel is declared
# desynced (crypto-desync incident + channel break -> the router's
# requeue/failover path).  The class is {"auth", "magic"} — wrong
# session keys fail AEAD verification on every frame, while rotation
# races surface as epoch-* rejects and injected faults as "fault",
# neither of which means the peer holds the wrong key.
CRYPTO_DESYNC_THRESHOLD = 3
_DESYNC_REASONS = frozenset({"auth", "magic"})

# one RPC may legitimately take this long (a worker's first RPC waits
# out its whole jax+daemon bring-up)
READY_TIMEOUT_S = 300.0
# the fallback bound for an op missing from nodehost.OP_TIMEOUTS —
# CTA011 keeps that table total, so this only covers test fakes
CTRL_TIMEOUT_S = 60.0


def spawn_available() -> bool:
    """Process mode needs the ``spawn`` start method (fork would
    duplicate the parent's jax runtime state into the child — the
    classic fork-after-init trap).  Tests skip cleanly when the
    platform lacks it."""
    try:
        import multiprocessing as mp

        return "spawn" in mp.get_all_start_methods()
    except Exception:  # noqa: BLE001 — no multiprocessing at all
        return False


def _refuse_accelerator_workers(config) -> None:
    """A chip belongs to one process, and this parent already holds
    every accelerator JAX found: a spawned tpu-backend worker would
    either fail to load the TPU library or wait out
    ``READY_TIMEOUT_S``.  Refuse at spawn instead.  On the cpu platform
    workers share the host, as the tests run them."""
    if config.backend != "tpu":
        return
    import jax

    devices = jax.devices()
    if devices[0].platform != "cpu":
        raise RuntimeError(
            f"process-mode cluster workers need a chip each, but this "
            f"process holds all {len(devices)} {devices[0].platform} "
            f"device(s) of the host; use thread mode, or "
            f"backend='interpreter' workers")


class ProcessNodeSpawner:
    """Owns the cluster's rendezvous listener and spawns workers.

    One listener serves every node: each worker dials back twice
    (control + data) introducing itself with a hello frame carrying
    the cluster token (a secret minted per ``ClusterServing`` — a
    stray dialer on the loopback port cannot join the cluster)."""

    def __init__(self):
        self.token = secrets.token_hex(16)
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET,
                              socket.SO_REUSEADDR, 1)
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(64)
        self.host, self.port = self._sock.getsockname()

    def spawn(self, name: str, config, kv_addr,
              parent_pub: Optional[str] = None,
              epoch: int = 0) -> "ProcessNode":
        """Launch one worker process (daemon bring-up runs in the
        child; :meth:`ProcessNode.wait_ready` blocks on it).
        ``parent_pub`` (hex) arms the encrypted data channel: the
        worker mints its own X25519 keypair, advertises the pubkey in
        its hello frames, and seals/opens every data-channel frame;
        ``epoch`` is the cluster's CURRENT key epoch so a scale-out
        worker joins mid-rotation-history at the right keys."""
        import multiprocessing as mp

        from .nodehost import node_host_main

        _refuse_accelerator_workers(config)
        # the worker's daemon must self-identify as ITS node (thread
        # mode does the same via dataclasses.replace): the flight
        # recorder stamps bundles with it, and a cluster sysdump
        # where every worker claims to be node0 is unusable
        cfg_fields = dataclasses.asdict(
            dataclasses.replace(config, node_name=name))
        ctx = mp.get_context("spawn")
        proc = ctx.Process(
            target=node_host_main,
            args=(self.host, self.port, self.token, name,
                  cfg_fields, tuple(kv_addr), parent_pub,
                  int(epoch)),
            daemon=True, name=f"cluster-node-{name}")
        proc.start()
        return ProcessNode(name, proc, self)

    def accept_channels(self, name: str, timeout: float = 60.0
                        ) -> Tuple[socket.socket, socket.socket,
                                   socket.socket, Optional[str]]:
        """Accept until all three of ``name``'s channels arrived
        (workers race; hellos disambiguate).  Returns the sockets
        plus the worker's advertised X25519 pubkey (hex, or None for
        a plaintext worker) — the spawn-handshake half of the
        encrypted-channel key exchange (ISSUE 18)."""
        got: Dict[str, socket.socket] = {}
        pubkey: Optional[str] = None
        deadline = time.monotonic() + timeout
        while not {"ctrl", "data", "obs"} <= set(got):
            self._sock.settimeout(max(deadline - time.monotonic(),
                                      0.01))
            try:
                sock, _addr = self._sock.accept()
            except socket.timeout:
                raise ServingError(
                    f"worker {name} never dialed home") from None
            sock.settimeout(30.0)
            try:
                hello = recv_json_frame(sock)
            except Exception:  # noqa: BLE001 — garbage dialer
                shutdown_close(sock)
                continue
            if (not hello or hello.get("token") != self.token
                    or hello.get("node") != name
                    or hello.get("role") not in ("ctrl", "data",
                                                 "obs")):
                shutdown_close(sock)
                continue
            sock.settimeout(None)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            got[hello["role"]] = sock
            if hello.get("pubkey"):
                pubkey = hello["pubkey"]
        return got["ctrl"], got["data"], got["obs"], pubkey

    def close(self) -> None:
        shutdown_close(self._sock)


class ProcessNode:
    """One worker-process replica behind the ClusterNode interface.

    Control RPCs are strict request/response, serialized by
    ``_ctrl_lock`` (a timed-out call marks the channel broken — the
    byte stream has lost sync — and every later call fails fast,
    which is what turns a wedged worker into probe failures and so
    into membership death).  The data channel belongs to this node's
    router forwarder thread alone."""

    # guarded-by: _lock: alive, final, _ct_snap_rows, _last_ack,
    # guarded-by: _lock: _crash_loss_pending, _frames, _bytes,
    # guarded-by: _lock: _frames_packed, _acks, _acks_coalesced
    # guarded-by: _lock: _crypto_nacks, _crypto_replays,
    # guarded-by: _lock: _crypto_open_failures, _open_fail_run
    # guarded-by: _win_cv: _win, _win_broken, _window_stalls,
    # guarded-by: _win_cv: _ord_sent, _ord_map

    def __init__(self, name: str, proc, spawner: ProcessNodeSpawner):
        self.idx = -1  # assigned by ClusterServing
        self.name = name
        self.proc = proc
        self._spawner = spawner
        self._lock = threading.Lock()
        self._ctrl_lock = threading.Lock()
        # the OBS channel gets its own socket + lock + broken flag:
        # a slow/timed-out scrape desyncs (and so breaks) only the
        # obs stream — membership probes ride ctrl untouched, so
        # observability can never get a healthy node declared dead
        self._obs_lock = threading.Lock()
        self._ctrl: Optional[socket.socket] = None
        self._data: Optional[socket.socket] = None
        self._obs: Optional[socket.socket] = None
        self._ctrl_broken: Optional[str] = None
        self._obs_broken: Optional[str] = None
        self.alive = True
        self.final: Optional[dict] = None
        self.kv_client = None  # the worker owns its kv client
        self.policy_sync = None  # likewise (polled over control)
        self._ct_snap_rows: Optional[np.ndarray] = None
        # (submitted, verdicts, shed, recovery_dropped) at last ack
        self._last_ack: Tuple[int, int, int, int] = (0, 0, 0, 0)
        self._crash_loss_pending = 0
        self._frames = 0
        self._frames_packed = 0
        self._bytes = 0
        # -- pipelined mode (ISSUE 17): send window + ack reader
        self._win: Optional[SendWindow] = None
        self._win_cv = threading.Condition()
        self._win_broken: Optional[str] = None
        self._window_stalls = 0
        self._acks = 0
        self._acks_coalesced = 0
        self._on_ack = None
        self._on_broken = None
        self._ack_thread: Optional[threading.Thread] = None
        # -- encrypted mode (ISSUE 18): parent half of the sealed
        # data channel.  peer_pub_hex arrives with the spawn
        # handshake; enable_crypto builds the channel before any
        # frame flows.
        self.peer_pub_hex: Optional[str] = None
        self._crypto = None  # encryption.EncryptedChannel
        self._crypto_grace_s = 0.0
        self._on_reject = None  # router's crypto-drop accounting
        self._crypto_nacks = 0  # worker-side rejects (NACK records)
        self._crypto_replays = 0  # NACKs with reason "replay"
        self._crypto_open_failures = 0  # parent-side open failures
        self._open_fail_run = 0  # consecutive key-mismatch failures
        self._ord_sent = 0  # sealed data frames sent (NACK ordinals)
        # ordinal -> window seq for sealed windowed frames; the
        # worker cannot read a rejected frame's seq (it is inside the
        # sealed payload), so its NACK carries the frame's receipt
        # ORDINAL instead — TCP ordering makes the parent's Nth send
        # the worker's Nth receipt, and this map turns the ordinal
        # back into the window entry whose rows the reject dropped
        self._ord_map: "collections.OrderedDict[int, int]" = \
            collections.OrderedDict()
        # replay test hook + wire-identity probe (the exact bytes of
        # the most recent data frame as they left for the socket)
        self._last_wire: Optional[bytes] = None

    # -- bring-up ------------------------------------------------------
    def attach(self, timeout: float = 60.0) -> None:
        (self._ctrl, self._data, self._obs,
         self.peer_pub_hex) = self._spawner.accept_channels(
            self.name, timeout)

    # -- encrypted mode (ISSUE 18) -------------------------------------
    def enable_crypto(self, keypair, peer_pub: bytes,
                      grace_s: float = 0.0, epoch: int = 0) -> None:
        # thread-affinity: api -- ClusterServing._build_node, before
        # any data frame flows on the channel
        """Arm the parent half of the sealed data channel: every
        frame this node sends or receives on the data socket is one
        AEAD seal/open.  ``epoch`` > 0 joins the channel at the
        cluster's current key epoch (scale-out under rotation)."""
        from ..encryption import EncryptedChannel

        self._crypto = EncryptedChannel(keypair, peer_pub,
                                        epoch=int(epoch))
        self._crypto_grace_s = float(grace_s)

    def set_reject_cb(self, cb) -> None:
        # thread-affinity: api -- router.start, before frames flow.
        """``cb(n_rows, reason, ctx)`` per worker crypto-reject —
        the router's ``crypto_dropped`` ledger term."""
        self._on_reject = cb

    def rotate_channel(self, epoch: int,
                       grace_s: Optional[float] = None) -> None:
        # thread-affinity: api -- ClusterServing.rotate_epoch (the
        # channel's own lock serializes against in-flight seal/open)
        ch = self._crypto
        if ch is None:
            return
        ch.rotate(int(epoch), self._crypto_grace_s
                  if grace_s is None else float(grace_s))

    def rotate_epoch(self, epoch: int,
                     grace_s: Optional[float] = None) -> dict:
        """One node's leg of the cluster-wide key rotation, in the
        two-phase order that closes BOTH directions at every
        interleaving: (1) the parent PRE-INSTALLS the new epoch's
        receive key (``prepare_recv``) so an ack the worker seals
        at e+1 right after its own rotate — while this control call
        is still in flight — opens instead of rejecting
        ``epoch-ahead`` (a discarded cumulative ack that covered
        the whole send window would wedge the channel's credit);
        (2) the worker rotates, parking the old epoch in its grace
        window so the parent's in-flight e-sealed data frames still
        open, and acks over control; (3) the parent channel
        rotates, adopting the prepared replay window."""
        g = (self._crypto_grace_s if grace_s is None
             else float(grace_s))
        ch = self._crypto
        if ch is not None:
            ch.prepare_recv(int(epoch))
        resp = self.call("rotate_epoch", epoch=int(epoch), grace_s=g)
        self.rotate_channel(epoch, g)
        return resp

    def _note_open_failure(self, exc: Exception) -> bool:
        # thread-affinity: transport, api -- the data-channel reader
        # (forwarder in sync mode, ack reader in pipelined mode);
        # api only via the quiesced inject_replay test hook

        """Count one parent-side open failure; True when this one
        crossed the key-desync threshold (the caller breaks the
        channel — counted degradation, never a hang)."""
        reason = getattr(exc, "reason", "fault")
        with self._lock:
            self._crypto_open_failures += 1
            if reason in _DESYNC_REASONS:
                self._open_fail_run += 1
                run = self._open_fail_run
            else:
                run = 0
        if run == CRYPTO_DESYNC_THRESHOLD:
            from ..obs.flightrec import KIND_CRYPTO_DESYNC

            self.record_incident(KIND_CRYPTO_DESYNC, {
                "node": self.name, "consecutive-failures": run,
                "reason": reason})
            return True
        return False

    def _count_nack(self, reason: str) -> None:
        # thread-affinity: transport, api -- api only via the
        # quiesced inject_replay test hook
        with self._lock:
            self._crypto_nacks += 1
            if reason == "replay":
                self._crypto_replays += 1

    def _open_sync_ack(self, ack: bytes, n_rows: int, trace
                       ) -> Tuple[Optional[bytes], int]:
        # thread-affinity: transport -- the sync submit path
        """Open one sync-mode ack frame.  Returns ``(plaintext,
        0)`` when the caller should parse the ack, or ``(None,
        count)`` when the frame resolved the submit here: a worker
        crypto-reject (rows dropped and counted) or a parent-side
        open failure (counted; delivered-or-dropped decided by the
        failure class — see below)."""
        ch = self._crypto
        try:
            plain = ch.open(ack)
            with self._lock:
                self._open_fail_run = 0
        except (DecryptError, InjectedFault) as exc:
            if is_crypto_reject(ack):
                # RAW reject record: the worker's reject-seal leg
                # faulted and it shipped the record unauthenticated.
                # Accept it for LOSS ACCOUNTING only — a forged one
                # can reclassify loss, never admit traffic — because
                # dropping it here would leave the rejected frame's
                # rows in no counter at all (silent loss)
                plain = ack
            else:
                return self._account_sync_open_failure(exc, n_rows,
                                                       trace)
        if not is_crypto_reject(plain):
            return plain, 0
        _ordn, reason = unpack_crypto_reject(plain)
        self._count_nack(reason)
        cb = self._on_reject
        if cb is not None:
            cb(n_rows, reason, trace)
        return None, 0

    def _account_sync_open_failure(self, exc: Exception, n_rows: int,
                                   trace) -> Tuple[None, int]:
        # thread-affinity: transport -- _open_sync_ack's failure leg
        reason = getattr(exc, "reason", "fault")
        if self._note_open_failure(exc):
            with self._win_cv:
                if self._win_broken is None:
                    self._win_broken = "crypto-desync"
        if reason in _DESYNC_REASONS:
            # wrong keys are SYMMETRIC (both directions derive from
            # the same shared secret): the worker cannot have opened
            # our data frame either — this response is its NACK,
            # unreadable.  Count the rows dropped; sync mode's 1:1
            # frame:response keeps that exact.
            cb = self._on_reject
            if cb is not None:
                cb(n_rows, reason, trace)
            return None, 0
        # outside the key-mismatch class (an injected open fault, a
        # rotation-race epoch reject): the worker DID open and admit
        # the frame — its own counters own these rows.  Skip the
        # _last_ack update; acked ledgers are cumulative, so the
        # next readable ack repairs it.
        return None, n_rows

    def wait_ready(self, timeout: float = READY_TIMEOUT_S) -> None:
        self.call("ready", timeout=timeout)

    # -- control / obs RPC ---------------------------------------------
    def _rpc(self, channel: str, op: str,
             timeout: Optional[float], args: dict) -> dict:
        # thread-affinity: any -- the per-channel lock serializes
        # callers (control-plane threads and any-affine readers
        # alike); a broken channel fails every later call fast (the
        # byte stream lost sync)
        if timeout is None:
            # the per-op bound table (nodehost.OP_TIMEOUTS, CTA011-
            # enforced total): every control RPC is bounded even
            # when the caller states no deadline of its own
            timeout = OP_TIMEOUTS.get(op, CTRL_TIMEOUT_S)
        lock = self._obs_lock if channel == "obs" \
            else self._ctrl_lock
        broken_attr = ("_obs_broken" if channel == "obs"
                       else "_ctrl_broken")
        with lock:
            broken = getattr(self, broken_attr)
            if broken is not None:
                raise ServingError(
                    f"{channel} channel to {self.name} broken: "
                    f"{broken}")
            sock = self._obs if channel == "obs" else self._ctrl
            if sock is None:
                raise ServingError(
                    f"worker {self.name} not attached")
            req = dict(args)
            req["op"] = op
            try:
                sock.settimeout(timeout)
                send_json_frame(sock, req)
                resp = recv_json_frame(sock)
            except Exception as exc:  # noqa: BLE001 — timeout, EOF,
                # torn frame: the stream lost sync either way
                setattr(self, broken_attr,
                        f"{type(exc).__name__}: {exc}")
                raise ServingError(
                    f"{channel} call {op!r} to {self.name} failed: "
                    f"{getattr(self, broken_attr)}") from None
            if resp is None:
                setattr(self, broken_attr, "EOF")
                raise ServingError(
                    f"worker {self.name} hung up mid-call ({op})")
            if "e" in resp:
                raise ServingError(
                    f"worker {self.name} {op} error: {resp['e']}")
            return resp

    def call(self, op: str, timeout: Optional[float] = None,
             **args) -> dict:
        return self._rpc("ctrl", op, timeout, args)

    def obs_call(self, op: str, timeout: Optional[float] = None,
                 **args) -> dict:
        """Observability RPC on the DEDICATED obs channel: a scrape
        that times out breaks only this stream — probes and failover
        control keep their own (ISSUE 14 review hardening)."""
        return self._rpc("obs", op, timeout, args)

    # -- the ClusterNode interface ------------------------------------
    def submit(self, rows: np.ndarray, trace=None,
               t_enq: Optional[float] = None) -> int:
        # (unannotated on purpose: inherits the router forwarder's
        # affinity, like ClusterNode.submit — the socket leg is the
        # transport domain's territory via the framing helpers)
        """Forward one chunk over the data channel.  SYNC mode (no
        window enabled — the PR 13 protocol, byte-identical): send
        one unsequenced frame, block for its per-frame ack.
        PIPELINED mode (``enable_window`` called — ISSUE 17): block
        only while the send window is FULL (credit backpressure),
        then send a sequenced frame and return; the ack reader
        retires it when the worker's cumulative ack arrives.  In
        both modes the per-node forwarder is the only caller.  Packs
        eligible single-stream chunks to the 16 B/packet wire.
        ``trace`` (an ``obs.relay.TraceCtx`` with t_enq/t_fwd
        stamped) rides the frame; the worker's recv/admit stamps
        come back on the (possibly coalesced) ack echo (ISSUE 14
        cross-process span stitching)."""
        from ..core.packets import pack_eligibility, pack_rows

        sock = self._data
        if sock is None:
            raise ServingError(f"worker {self.name} not attached")
        with self._win_cv:
            win = self._win
            if self._win_broken is not None:
                # a desynced (or otherwise dead) channel fails every
                # submit fast — the forwarder's requeue owns the rows
                raise ServingError(
                    f"data channel to {self.name} broken: "
                    f"{self._win_broken}")
        ch = self._crypto
        wire_trace = ((trace.trace_id, trace.t_enq, trace.t_fwd)
                      if trace is not None else None)
        ok, ep, dirn = pack_eligibility(rows)
        wire_rows = pack_rows(rows) if ok else rows
        meta = (ep, dirn) if ok else None
        if win is None:
            payload = encode_rows(wire_rows, packed_meta=meta,
                                  trace=wire_trace)
            if ch is not None:
                try:
                    payload = ch.seal(payload)
                except InjectedFault as exc:
                    # the frame never reached the wire: the
                    # forwarder's requeue-on-error owns these rows
                    raise ServingError(
                        f"seal to {self.name} failed: "
                        f"{exc}") from None
            send_frame(sock, payload)
            if ch is not None:
                with self._win_cv:
                    self._ord_sent += 1
            self._last_wire = payload
            ack = recv_frame(sock)
            if ack is None:
                raise ServingError(
                    f"worker {self.name} closed the data channel")
            if ch is not None:
                ack, shortcut = self._open_sync_ack(ack, len(rows),
                                                    trace)
                if ack is None:
                    return shortcut
            (admitted, sub, ver, shed, rec), echo = unpack_ack_ex(ack)
            if trace is not None and echo is not None \
                    and echo[0] == trace.trace_id:
                trace.t_recv, trace.t_admit = echo[1], echo[2]
            with self._lock:
                self._last_ack = (sub, ver, shed, rec)
                self._frames += 1
                self._frames_packed += 1 if ok else 0
                self._bytes += len(payload)
            return admitted
        # pipelined: wait for credit, register, send, return.  The
        # entry registers BEFORE the send so a cumulative ack racing
        # the sendall's return can never arrive for a frame the
        # window does not know; a FAILED send unregisters it (the
        # frame never reached the worker — the forwarder's requeue
        # owns those rows alone).
        with self._win_cv:
            if win.full:
                self._window_stalls += 1
                while win.full and self._win_broken is None:
                    self._win_cv.wait(0.5)
            if self._win_broken is not None:
                raise ServingError(
                    f"data channel to {self.name} broken: "
                    f"{self._win_broken}")
            seq = win.add(rows, t_enq if t_enq is not None
                          else time.monotonic(), trace)
        payload = encode_rows(wire_rows, packed_meta=meta,
                              trace=wire_trace, seq=seq)
        ordn = None
        if ch is not None:
            try:
                payload = ch.seal(payload)
            except InjectedFault as exc:
                # never reached the wire: unwind the window entry
                # and let the forwarder's requeue own the rows
                with self._win_cv:
                    win.drop(seq)
                    self._win_cv.notify_all()
                raise ServingError(
                    f"seal to {self.name} failed: {exc}") from None
            # register BEFORE the send (like win.add): a NACK racing
            # the sendall's return must find its ordinal mapped
            with self._win_cv:
                self._ord_sent += 1
                ordn = self._ord_sent
                self._ord_map[ordn] = seq
        self._last_wire = payload
        try:
            send_frame(sock, payload)
        except Exception as exc:  # noqa: BLE001 — dead fd mid-send
            with self._win_cv:
                win.drop(seq)
                if ordn is not None:
                    self._ord_map.pop(ordn, None)
                self._win_cv.notify_all()
            raise ServingError(
                f"send to {self.name} failed: "
                f"{type(exc).__name__}: {exc}") from None
        with self._lock:
            self._frames += 1
            self._frames_packed += 1 if ok else 0
            self._bytes += len(payload)
        return len(rows)

    # -- pipelined mode (ISSUE 17) -------------------------------------
    def enable_window(self, window: int, on_ack=None,
                      on_broken=None) -> None:
        # thread-affinity: api -- router.start / router.add_node,
        # before any frame flows on the channel
        """Switch the data channel to pipelined mode: a send window
        of ``window`` frames and a dedicated ack-reader thread.
        ``on_ack(entries)`` fires with the retired
        ``(n_rows, t_enq, ctx)`` list per cumulative ack (the
        router's credit return + latency/span accounting);
        ``on_broken(entries)`` fires ONCE with every sent-but-unacked
        ``(rows, t_enq, ctx)`` when the channel dies (the router
        requeues them for failover)."""
        if window < 2:
            return  # window 1 IS the sync protocol; keep it exact
        with self._win_cv:
            if self._win is not None:
                return
            self._win = SendWindow(window)
            self._on_ack = on_ack
            self._on_broken = on_broken
        self._ack_thread = threading.Thread(
            target=self._ack_read_loop, daemon=True,
            name=f"cluster-ack-{self.name}")
        self._ack_thread.start()

    def _ack_read_loop(self) -> None:
        # thread-affinity: transport -- the parent's half of the
        # coalesced-ack channel: recv, retire, return credit.  On
        # ANY exit every in-flight frame is handed back to the
        # router exactly once (requeue or counted loss — never
        # silent).
        sock = self._data
        with self._win_cv:
            win = self._win
        ch = self._crypto
        try:
            while True:
                payload = recv_frame(sock)
                if payload is None:
                    break
                if ch is not None:
                    raw = payload
                    try:
                        payload = ch.open(payload)
                        with self._lock:
                            self._open_fail_run = 0
                    except (DecryptError, InjectedFault) as exc:
                        if is_crypto_reject(raw):
                            # RAW reject fallback (the worker's
                            # reject-seal leg faulted): accept it for
                            # loss accounting only — see
                            # _open_sync_ack — else the rejected
                            # frame's rows land in no counter
                            payload = raw
                        elif self._note_open_failure(exc):
                            # key desync: break the channel so the
                            # finally's take_all hands every
                            # in-flight frame back to the router
                            # (requeued and counted — never silent,
                            # never a hang)
                            with self._win_cv:
                                if self._win_broken is None:
                                    self._win_broken = "crypto-desync"
                            break
                        else:
                            continue
                    if is_crypto_reject(payload):
                        # the worker could not open our Nth data
                        # frame: pop exactly that window entry — its
                        # rows are a counted, flow-visible drop, NOT
                        # a requeue (the frame reached the worker)
                        ordn, reason = unpack_crypto_reject(payload)
                        with self._win_cv:
                            seq = self._ord_map.pop(ordn, None)
                            ent = (win.pop(seq) if seq is not None
                                   else None)
                            self._win_cv.notify_all()
                        self._count_nack(reason)
                        cb = self._on_reject
                        if cb is not None:
                            cb(len(ent[1]) if ent is not None else 0,
                               reason,
                               ent[3] if ent is not None else None)
                        continue
                (seq, frames, _admitted, sub, ver, shed,
                 rec), echoes = unpack_cum_ack(payload)
                with self._win_cv:
                    entries = win.retire(seq)
                    # ordinals the cumulative ack covered can never
                    # be NACKed again — prune the map from the front
                    # (insertion order == seq order)
                    while self._ord_map and next(iter(
                            self._ord_map.values())) <= seq:
                        self._ord_map.popitem(last=False)
                    self._win_cv.notify_all()
                with self._lock:
                    self._last_ack = (sub, ver, shed, rec)
                    self._acks += 1
                    self._acks_coalesced += max(int(frames) - 1, 0)
                if echoes:
                    by_tid = {e[0]: e for e in echoes}
                    for _s, _rows, _t_enq, ctx in entries:
                        if ctx is not None:
                            e = by_tid.get(ctx.trace_id)
                            if e is not None:
                                ctx.t_recv, ctx.t_admit = e[1], e[2]
                cb = self._on_ack
                if cb is not None and entries:
                    cb([(len(r), t_enq, ctx)
                        for _s, r, t_enq, ctx in entries])
        except Exception:  # noqa: BLE001 — torn frame/dead fd: the
            pass  # channel contract is dead; the finally owns the
            # in-flight hand-back
        finally:
            with self._win_cv:
                if self._win_broken is None:
                    self._win_broken = "data channel closed"
                entries = win.take_all()
                self._win_cv.notify_all()
            cb = self._on_broken
            if cb is not None and entries:
                cb([(r, t_enq, ctx)
                    for _s, r, t_enq, ctx in entries])

    def drain_window(self, timeout: float = 30.0) -> bool:
        # thread-affinity: api
        """Block until every in-flight frame is acked (True) or the
        channel broke / ``timeout`` ran out (False when frames were
        still pending).  The quiesce primitive for stop/scale-in:
        "drained" now means the WINDOW is empty, not just the
        queues."""
        deadline = time.monotonic() + timeout
        with self._win_cv:
            win = self._win
            if win is None:
                return True
            while win.inflight_frames and self._win_broken is None:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._win_cv.wait(min(left, 0.1))
            return win.inflight_frames == 0

    def window_inflight(self) -> Tuple[int, int]:
        # thread-affinity: any
        """(frames, rows) currently sent-but-unacked."""
        with self._win_cv:
            win = self._win
            if win is None:
                return (0, 0)
            return (win.inflight_frames, win.inflight_rows)

    def inject_replay(self) -> bool:
        # thread-affinity: api -- TEST HOOK (chaos gate): call only
        # on a quiesced channel (no forwarder traffic in flight)
        """Re-send the last sealed data frame VERBATIM — the
        replay-attack injection.  The worker's per-epoch replay
        window must reject it (counted, NACKed, zero rows dropped —
        the original already resolved).  True when the replay was
        rejected as a replay."""
        wire = self._last_wire
        sock = self._data
        if wire is None or self._crypto is None or sock is None:
            return False
        send_frame(sock, wire)
        with self._win_cv:
            self._ord_sent += 1
            win = self._win
        if win is not None:
            return True  # the ack reader counts the NACK
        # sync protocol: consume the reject reply in-line
        resp = recv_frame(sock)
        if resp is None:
            return False
        try:
            resp = self._crypto.open(resp)
        except (DecryptError, InjectedFault) as exc:
            self._note_open_failure(exc)
            return False
        if not is_crypto_reject(resp):
            return False
        _ordn, reason = unpack_crypto_reject(resp)
        self._count_nack(reason)
        cb = self._on_reject
        if cb is not None:
            cb(0, reason, None)
        return reason == "replay"

    def ack_flush(self) -> Optional[dict]:
        # thread-affinity: api
        """Ask the worker's coalescer to flush NOW (collapses the
        flush-timer tail out of a drain) and return its counters."""
        try:
            return self.call("ack_flush", timeout=10.0)
        except ServingError:
            return None

    def probe(self) -> bool:
        # thread-affinity: api
        """Liveness over the control channel: the worker process is
        running AND its drain loop answers.  A control timeout (a
        wedged worker) reads as dead — which is the point."""
        with self._lock:
            if not self.alive:
                return False
        if not self.proc.is_alive():
            return False
        try:
            return bool(self.call("probe", timeout=5.0)["ok"])
        except ServingError:
            return False

    def crash(self, cause: str) -> None:
        # thread-affinity: api
        """Real node death: SIGKILL the worker (no goodbye, no final
        snapshot — the honest failure mode).  ``final`` becomes the
        last ack's ledger; the admitted-but-unresolved delta parks in
        ``_crash_loss_pending`` for the failover path to hand to
        ``router.account_crash_loss``.  Closing the sockets wakes a
        forwarder blocked in the ack wait (shutdown-before-close),
        whose requeue-on-error path keeps its in-flight chunk
        counted."""
        with self._lock:
            if not self.alive:
                return
            self.alive = False
            sub, ver, shed, rec = self._last_ack
            self.final = {"front-end": {
                "submitted": sub,
                "verdicts": ver,
                "shed": shed,
                "fault-tolerance": {"recovery-dropped": rec},
                "crash": cause,
            }}
            self._crash_loss_pending = max(
                sub - (ver + shed + rec), 0)
        try:
            self.proc.kill()  # SIGKILL — not terminate()'s SIGTERM
        except Exception:  # noqa: BLE001 — already gone
            pass
        shutdown_close(self._data)
        shutdown_close(self._obs)
        shutdown_close(self._ctrl)
        with self._ctrl_lock:
            self._ctrl_broken = f"killed: {cause}"
        with self._obs_lock:
            self._obs_broken = f"killed: {cause}"
        # pipelined mode: the closed fd EOFs the ack reader, whose
        # exit path hands every sent-but-unacked frame back to the
        # router (on_broken requeue).  JOIN it before returning so
        # the failover that called crash() migrates a queue that
        # already contains them — mid-window SIGKILL loses nothing.
        t = self._ack_thread
        if t is not None:
            t.join(timeout=10.0)
        self.proc.join(timeout=10.0)

    def take_crash_loss(self) -> int:
        # thread-affinity: api
        """The admitted-but-unresolved row count from the last ack,
        exactly once (the failover path feeds it to
        ``router.account_crash_loss``)."""
        with self._lock:
            n, self._crash_loss_pending = self._crash_loss_pending, 0
            return n

    def mode(self) -> Optional[str]:
        # thread-affinity: any
        with self._lock:
            fin = self.final
        if fin is not None:
            return fin.get("mode")
        try:
            return self.call("front_end", timeout=10.0).get("mode")
        except ServingError:
            return None

    # -- node interface (failover / scale-out / surfaces) --------------
    def start_node(self) -> None:
        self.call("start_node")

    def warm(self, bucket: int, ep: int, trace_sample: int = 0,
             ring_capacity: int = 1 << 15) -> None:
        self.call("warm", timeout=READY_TIMEOUT_S, bucket=int(bucket),
                  ep=int(ep), trace_sample=int(trace_sample),
                  ring_capacity=int(ring_capacity))

    def start_serving(self, **kwargs) -> None:
        self.call("start_serving", timeout=READY_TIMEOUT_S,
                  kwargs=kwargs)

    def stop_serving(self) -> Optional[dict]:
        with self._lock:
            if not self.alive:
                return self.final
        try:
            fin = self.call("stop_serving",
                            timeout=READY_TIMEOUT_S)
        except ServingError:
            with self._lock:
                return self.final
        with self._lock:
            self.final = fin
        return fin

    def add_endpoint(self, name: str, ips, labels) -> int:
        return int(self.call("add_endpoint", name=name,
                             ips=list(ips),
                             labels=list(labels))["id"])

    def applied_policy_rev(self) -> int:
        try:
            return int(self.call("policy_rev", timeout=10.0)["rev"])
        except ServingError:
            return -1

    def has_identity(self, numeric: int) -> bool:
        try:
            return bool(self.call("has_identity", timeout=10.0,
                                  numeric=int(numeric))["ok"])
        except ServingError:
            return False

    def front_end(self) -> Optional[dict]:
        with self._lock:
            if not self.alive or self.final is not None:
                fin = self.final
                return fin.get("front-end") if fin else None
        try:
            return self.call("front_end", timeout=30.0).get(
                "front-end")
        except ServingError:
            return None

    def node_ledgers(self) -> Optional[dict]:
        """event/span/agg ledger blocks; the packet ledger rides
        ``front_end``.  ``None`` for a corpse — SIGKILL erases the
        in-process planes, which is exactly what the thread-mode
        tier could pretend it didn't (DIVERGENCES rewrite)."""
        # `final`, not `alive`, selects the retained ledgers: crash()
        # sets both under one lock, and a clean stop retains final
        # while the worker lives on
        with self._lock:
            fin = self.final
        if fin is not None:
            return fin.get("ledgers")
        try:
            return self.call("front_end", timeout=30.0).get("ledgers")
        except ServingError:
            return None

    def worker_crypto(self) -> Optional[dict]:
        """The WORKER half's channel counters (rx frames, rejects,
        replays, epoch — the parent half rides
        :meth:`transport_stats`); ``None`` on a plaintext cluster.
        The retained final survives a clean stop; SIGKILL erases the
        worker's counters with the process (the parent half is then
        the only surviving record of the channel)."""
        with self._lock:
            fin = self.final
        if fin is not None:
            return fin.get("crypto")
        try:
            return self.call("front_end", timeout=30.0).get("crypto")
        except ServingError:
            return None

    def l7_stats(self) -> Optional[dict]:
        """The node's L7 proxy-plane block (the worker ships it with
        ``front_end``; the retained final survives a clean stop —
        SIGKILL erases the pool with the process)."""
        with self._lock:
            fin = self.final
        if fin is not None:
            return fin.get("l7")
        try:
            return self.call("front_end", timeout=30.0).get("l7")
        except ServingError:
            return None

    def snapshot_ct(self, trigger: str = "cluster") -> np.ndarray:
        """Fan-out snapshot: the worker snapshots AND ships the rows;
        the parent-side replica is what failover replays after a
        SIGKILL."""
        rows = rows_from_b64(self.call("ct_snapshot",
                                   timeout=READY_TIMEOUT_S,
                                   trigger=trigger)["rows"])
        with self._lock:
            self._ct_snap_rows = rows
        return rows

    def ct_rows_for_failover(self) -> np.ndarray:
        from ..datapath.conntrack import ROW_WORDS

        with self._lock:
            snap = self._ct_snap_rows
        if snap is not None:
            return snap
        # no replicated snapshot: the corpse's device CT died with
        # its process — pre-failover connections re-establish
        return np.zeros((0, ROW_WORDS), dtype=np.uint32)

    def merge_ct(self, rows: np.ndarray) -> None:
        self.call("ct_merge", timeout=READY_TIMEOUT_S,
                  rows=rows_to_b64(rows))

    def record_incident(self, kind: str, rec: dict) -> None:
        try:
            self.call("record_incident", kind=kind, rec=rec)
        except ServingError:
            pass  # incident surfacing is advisory

    def publish_cluster_drops(self, rows: Optional[np.ndarray],
                              count: int) -> None:
        try:
            self.call("publish_drops", count=int(count),
                      rows=(rows_to_b64(rows) if rows is not None
                            and len(rows) else None))
        except ServingError:
            pass  # best-effort surfacing; the exact count lives in
            # router_overflow

    def metrics(self) -> Optional[np.ndarray]:
        try:
            return np.asarray(self.call("metricsmap",
                                        timeout=30.0)["metrics"])
        except ServingError:
            return None

    def metrics_text(self) -> Optional[str]:
        """The worker's self-describing registry exposition (the
        ``metrics`` op's ISSUE 14 shape)."""
        try:
            return self.call("metrics", timeout=30.0)["text"]
        except ServingError:
            return None

    # -- node obs interface (the relay's scrape surface) ---------------
    def obs_scrape(self, cursor: int = 0, flows: int = 512,
                   top: int = 16) -> dict:
        """One observability scrape over the DEDICATED obs channel —
        raises on failure (the relay counts it and serves
        last-known-good; swallowing here would make a dead worker
        look healthily empty)."""
        return self.obs_call("obs_scrape", cursor=int(cursor),
                             flows=int(flows), top=int(top))

    def sysdump_bundle(self, trigger: str = "cluster-sysdump"
                       ) -> dict:
        return self.obs_call("sysdump", trigger=trigger)["bundle"]

    def slo(self) -> dict:
        """This worker's node-stamped SLO verdict — raises on
        failure, like ``obs_scrape``: the relay's cluster verdict
        must COUNT an unreachable node, not skip it."""
        return self.obs_call("slo")

    def history(self, series=None, since: float = 0.0) -> dict:
        return self.obs_call(
            "history",
            series=list(series) if series is not None else None,
            since=float(since))

    def map_pressure(self) -> Optional[dict]:
        try:
            return self.call("map_pressure",
                             timeout=30.0)["pressure"]
        except ServingError:
            return None

    def dispatch_compiles(self) -> Optional[dict]:
        try:
            return self.call("compile_stats", timeout=30.0)
        except ServingError:
            return None

    def transport_stats(self) -> dict:
        with self._lock:
            out = {"frames": self._frames,
                   "frames-packed": self._frames_packed,
                   "bytes": self._bytes,
                   "acks": self._acks,
                   "acks-coalesced": self._acks_coalesced}
        with self._win_cv:
            win = self._win
            out["window"] = win.window if win is not None else 1
            out["inflight-frames"] = (win.inflight_frames
                                      if win is not None else 0)
            out["window-stalls"] = self._window_stalls
        ch = self._crypto
        if ch is not None:
            with self._lock:
                out["crypto"] = {
                    "epoch": ch.epoch,
                    "sealed": ch.sealed,
                    "opened": ch.opened,
                    # worker NACKs + every parent-side open failure
                    # (channel rejects and injected faults alike)
                    "rejected": (self._crypto_nacks
                                 + self._crypto_open_failures),
                    "nacks": self._crypto_nacks,
                    "open-failures": self._crypto_open_failures,
                    "replays": ch.replays + self._crypto_replays,
                    "rotations": ch.rotations,
                }
        return out

    def shutdown(self) -> None:
        with self._lock:
            was_alive = self.alive
        if was_alive:
            try:
                self.call("shutdown", timeout=30.0)
            except ServingError:
                pass
        shutdown_close(self._data)
        shutdown_close(self._obs)
        shutdown_close(self._ctrl)
        t = self._ack_thread
        if t is not None:
            t.join(timeout=5.0)
        self.proc.join(timeout=30.0)
        if self.proc.is_alive():
            self.proc.kill()
            self.proc.join(timeout=10.0)
