"""Training: data-parallel over the device mesh + synthetic labeled
traffic (the CIC-IDS2017-style replay stands in; the real dataset is
not shippable in-repo).

The train step runs under ``shard_map``: batch sharded over the
``data`` axis, params replicated, gradients ``psum``-ed — the classic
dp recipe.  Attack patterns synthesized: port scans (one source
sweeping many ports, tiny SYNs), volumetric floods (many sources, one
service), and exfiltration (huge egress transfers) against the benign
steady-state mix from ``testing.fixtures.bench_traffic``.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..core.packets import (
    COL_DIR,
    COL_DPORT,
    COL_DST_IP3,
    COL_EP,
    COL_FAMILY,
    COL_FLAGS,
    COL_LEN,
    COL_PROTO,
    COL_SPORT,
    COL_SRC_IP3,
    N_COLS,
    TCP_ACK,
    TCP_SYN,
)
from .model import AnomalyModel, bce_loss

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


ATTACK_KINDS = {0: "portscan", 1: "flood", 2: "exfil"}


def synth_labeled_traffic(world, n: int, rng: np.random.Generator,
                          attack_frac: float = 0.25,
                          kinds: Tuple[int, ...] = (0, 1, 2),
                          hard_negatives: bool = True,
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """-> (hdr [n, N_COLS] uint32, labels [n] float32 1=attack).

    ``kinds`` restricts which attack kinds appear (held-out-kind
    evaluation trains on a subset and tests generalization on the
    rest).  ``hard_negatives`` injects BENIGN traffic that resembles
    attacks along single features — reconnect storms (SYN bursts to a
    real service) and bulk transfers (MTU-size pushes on a well-known
    port) — so separability must come from feature conjunctions, not
    one trivial column."""
    import ipaddress

    from ..testing.fixtures import bench_traffic

    hdr = bench_traffic(world, n, rng)
    labels = np.zeros(n, dtype=np.float32)
    n_attack = int(n * attack_frac)
    idx = rng.choice(n, n_attack, replace=False)
    kind_of = rng.choice(np.asarray(kinds, dtype=np.int64), n_attack)
    ips = np.array([int(ipaddress.IPv4Address(ip))
                    for ip in world.pod_ips], dtype=np.uint32)
    scanner = ips[0]
    victim = ips[1]
    for i, kind in zip(idx, kind_of):
        labels[i] = 1.0
        if kind == 0:  # port scan: tiny SYNs sweeping the port space
            hdr[i, COL_SRC_IP3] = rng.choice(ips[:8])  # several scanners
            hdr[i, COL_DPORT] = rng.integers(1, 65535)
            hdr[i, COL_FLAGS] = TCP_SYN
            hdr[i, COL_LEN] = rng.integers(40, 60)
            hdr[i, COL_PROTO] = 6
        elif kind == 1:  # flood: spoofed sources hammering one service
            hdr[i, COL_SRC_IP3] = rng.choice(ips)
            hdr[i, COL_DST_IP3] = victim
            hdr[i, COL_DPORT] = 80
            hdr[i, COL_FLAGS] = TCP_SYN
            hdr[i, COL_LEN] = rng.integers(40, 60)
            hdr[i, COL_PROTO] = 6
        else:  # exfiltration: huge egress pushes to odd ports
            hdr[i, COL_DIR] = 1
            hdr[i, COL_DPORT] = rng.integers(20000, 65000)
            hdr[i, COL_FLAGS] = TCP_ACK | 0x08  # PSH|ACK
            hdr[i, COL_LEN] = rng.integers(1400, 1500)
            hdr[i, COL_PROTO] = 6
    if hard_negatives:
        # benign rows that share single attack features
        benign = np.nonzero(labels == 0)[0]
        n_hard = len(benign) // 5
        hard = rng.choice(benign, n_hard, replace=False)
        half = n_hard // 2
        # reconnect storm: SYNs to a real service port, normal sizes
        storm = hard[:half]
        hdr[storm, COL_DPORT] = 5432
        hdr[storm, COL_FLAGS] = TCP_SYN
        hdr[storm, COL_LEN] = rng.integers(52, 80, len(storm))
        # bulk transfer: MTU-size PSH|ACK egress on a well-known port
        bulk = hard[half:]
        hdr[bulk, COL_DIR] = 1
        hdr[bulk, COL_DPORT] = 443
        hdr[bulk, COL_FLAGS] = TCP_ACK | 0x08
        hdr[bulk, COL_LEN] = rng.integers(1400, 1500, len(bulk))
    return hdr, labels


def make_train_step(optimizer, mesh: Optional[Mesh] = None,
                    axis: str = "data") -> Callable:
    """Build the jitted train step.  With a mesh: dp via shard_map
    (batch sharded, params replicated, grads psum'd)."""

    def _step(params, opt_state, id_row, feats, labels):
        loss, grads = jax.value_and_grad(bce_loss)(params, id_row,
                                                   feats, labels)
        if mesh is not None:
            grads = jax.tree.map(partial(jax.lax.pmean, axis_name=axis),
                                 grads)
            loss = jax.lax.pmean(loss, axis)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    if mesh is None:
        return jax.jit(_step)

    sharded = shard_map(
        _step, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis, None), P(axis)),
        out_specs=(P(), P(), P()),
    )
    return jax.jit(sharded)


def train(params: AnomalyModel, world, steps: int = 200,
          batch: int = 4096, lr: float = 3e-3,
          mesh: Optional[Mesh] = None, seed: int = 0,
          now: int = 1000,
          kinds: Tuple[int, ...] = (0, 1, 2)) -> Tuple[AnomalyModel, list]:
    """Train on synthetic labeled traffic run through the real
    datapath (features include CT state, so the model sees what the
    device sees).  ``kinds`` restricts the attack kinds seen in
    training (held-out-kind evaluation)."""
    from ..datapath.verdict import datapath_step
    from .features import flow_features

    rng = np.random.default_rng(seed)
    optimizer = optax.adam(lr)
    opt_state = optimizer.init(params)
    step_fn = make_train_step(optimizer, mesh)
    dp_step = jax.jit(datapath_step, donate_argnums=0)
    state = world.state
    losses = []
    for s in range(steps):
        hdr, labels = synth_labeled_traffic(world, batch, rng,
                                            kinds=kinds)
        jhdr = jnp.asarray(hdr)
        out, state = dp_step(state, jhdr, jnp.uint32(now + s))
        id_row, feats = flow_features(jhdr, out)
        params, opt_state, loss = step_fn(params, opt_state, id_row,
                                          feats, jnp.asarray(labels))
        losses.append(loss)  # stays on device: the training loop is
        # fetch-free (a per-step float() would wait on the device)
    world.state = state
    if losses:
        losses = [float(x) for x in np.asarray(jnp.stack(losses))]
    return params, losses


def auc(scores: np.ndarray, labels: np.ndarray) -> float:
    """ROC AUC by rank statistic (no sklearn dependency)."""
    scores = np.asarray(scores, dtype=np.float64)
    labels = np.asarray(labels)
    pos = scores[labels > 0.5]
    neg = scores[labels <= 0.5]
    if len(pos) == 0 or len(neg) == 0:
        return float("nan")
    order = np.argsort(np.concatenate([pos, neg]), kind="mergesort")
    ranks = np.empty(len(order), dtype=np.float64)
    ranks[order] = np.arange(1, len(order) + 1)
    # average ties
    allscores = np.concatenate([pos, neg])
    sorted_scores = allscores[order]
    i = 0
    while i < len(sorted_scores):
        j = i
        while j + 1 < len(sorted_scores) and \
                sorted_scores[j + 1] == sorted_scores[i]:
            j += 1
        if j > i:
            ranks[order[i:j + 1]] = (i + 1 + j + 1) / 2.0
        i = j + 1
    r_pos = ranks[:len(pos)].sum()
    n_pos, n_neg = len(pos), len(neg)
    return float((r_pos - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))
