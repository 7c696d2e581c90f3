"""Plain reference of the first chunk of Hetero-SplitEE rounds on the
paper's Table-I ResNet-18 (arXiv:2512.09313, Alg. 1 and Alg. 2).

Straight ``jax.numpy``, one client at a time, with every convolution and
matrix product at ``Precision.HIGHEST``.  It imports nothing of the program
and takes nothing the program has made: it draws its own weights from the
seed, by the recipe the paper's code states (every layer truncated-normal
at 2 sigma with std 1/sqrt(fan_in), BatchNorm scale 1 and bias 0, running
mean 0 and variance 1, the client exit head of cut ``l`` from seed
``seed + 1000 + l``), and it is fed the batches of ``bench/traffic/iid_images.py``.

One round, per client ``i`` with cut ``l_i``, in client order:

  * client step: layers 1..l_i, the exit head (global average pool + fc),
    cross-entropy, Adam on the client's parameters;
  * server step on ``h`` as data (no gradient reaches the client): layers
    l_i+1..6 and the server head, cross-entropy, Adam with the server rate
    (the rate over N under Sequential, Table II);
  * Averaging: per-client servers, then Eq. (1): every server layer and the
    server head, parameters and BatchNorm statistics, is replaced by its
    mean over the clients whose server holds it.  Sequential: one shared
    server, cut at the smallest l_i, stepped by every client in turn.

BatchNorm runs in training mode (batch statistics; running statistics move
with momentum 0.9).  Adam: b1 0.9, b2 0.999, eps 1e-8, bias-corrected, no
weight decay; the rate is cosine-annealed per round.

``dtype`` bfloat16 gives the control of the correctness check: the same
reference with its weights, moments, batches and arithmetic in bfloat16.
``fault`` plants a fault in the reference put in the program's place:
``"half"`` (each loss is the mean over the first half of the batch),
``"label"`` (the first label of every batch altered where it is staged) or
``"noagg"`` (Eq. (1) left out).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FAULTS = ("half", "label", "noagg")


# ------------------------------------------------------------------- weights
def _trunc(key, shape, fan_in):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * (1.0 / math.sqrt(max(1, fan_in))))


def _bn(c):
    return ({"scale": jnp.ones((c,), jnp.float32),
             "bias": jnp.zeros((c,), jnp.float32)},
            {"mean": jnp.zeros((c,), jnp.float32),
             "var": jnp.ones((c,), jnp.float32)})


def init_network(seed: int, channels: Sequence[int], num_classes: int):
    """(params, bn_state) of the whole network, keyed layer1..layerL, head."""
    ks = jax.random.split(jax.random.PRNGKey(seed), len(channels) + 1)
    params, state = {}, {}
    p1 = {"conv": _trunc(ks[0], (3, 3, 3, channels[0]), 27)}
    p1["bn"], s1 = _bn(channels[0])
    params["layer1"], state["layer1"] = p1, {"bn": s1}
    cin = channels[0]
    for i in range(1, len(channels)):
        cout = channels[i]
        kb = jax.random.split(ks[i], 3)
        p = {"conv1": _trunc(kb[0], (3, 3, cin, cout), 9 * cin),
             "conv2": _trunc(kb[1], (3, 3, cout, cout), 9 * cout)}
        s = {}
        p["bn1"], s["bn1"] = _bn(cout)
        p["bn2"], s["bn2"] = _bn(cout)
        if cin != cout:
            p["proj"] = _trunc(kb[2], (1, 1, cin, cout), cin)
            p["bn_proj"], s["bn_proj"] = _bn(cout)
        params[f"layer{i + 1}"], state[f"layer{i + 1}"] = p, s
        cin = cout
    params["head"] = {"w": _trunc(ks[-1], (cin, num_classes), cin),
                      "b": jnp.zeros((num_classes,), jnp.float32)}
    return params, state


def make_client(seed, params, state, channels, num_classes, li):
    layers = {f"layer{k}": params[f"layer{k}"] for k in range(1, li + 1)}
    key = jax.random.PRNGKey(seed + 1000 + li)
    out = {"w": _trunc(key, (channels[li - 1], num_classes),
                       channels[li - 1]),
           "b": jnp.zeros((num_classes,), jnp.float32)}
    return {"trainable": {"layers": layers, "out": out},
            "state": {f"layer{k}": state[f"layer{k}"]
                      for k in range(1, li + 1)}}


def make_server(params, state, num_layers, li):
    keep = [f"layer{k}" for k in range(li + 1, num_layers + 1)]
    tr = {k: params[k] for k in keep}
    tr["head"] = params["head"]
    return {"trainable": tr, "state": {k: state[k] for k in keep}}


# ------------------------------------------------------------------- forward
def _conv(x, w, stride):
    return lax.conv_general_dilated(x, w, (stride, stride), "SAME",
                                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                                    precision=HIGHEST)


def _batchnorm(p, s, x, momentum):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    new = {"mean": momentum * s["mean"] + (1 - momentum) * mean,
           "var": momentum * s["var"] + (1 - momentum) * var}
    y = (x - mean) * lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    return y, new


def features(params, state, x, strides, momentum, start, end):
    """Layers start+1..end in training mode; returns (h, new_state)."""
    new_state = dict(state)
    h = x
    for i in range(start, end):
        name = f"layer{i + 1}"
        p, s = params[name], state[name]
        if i == 0:
            h = _conv(h, p["conv"], strides[0])
            h, bn = _batchnorm(p["bn"], s["bn"], h, momentum)
            new_state[name] = {"bn": bn}
            h = jax.nn.relu(h)
            continue
        ns = {}
        out = _conv(h, p["conv1"], strides[i])
        out, ns["bn1"] = _batchnorm(p["bn1"], s["bn1"], out, momentum)
        out = _conv(jax.nn.relu(out), p["conv2"], 1)
        out, ns["bn2"] = _batchnorm(p["bn2"], s["bn2"], out, momentum)
        if "proj" in p:
            sc = _conv(h, p["proj"], strides[i])
            sc, ns["bn_proj"] = _batchnorm(p["bn_proj"], s["bn_proj"], sc,
                                           momentum)
        else:
            sc = h[:, ::strides[i], ::strides[i], :]
        h = jax.nn.relu(out + sc)
        new_state[name] = ns
    return h, new_state


def _head(p, h):
    return jnp.dot(jnp.mean(h, (1, 2)), p["w"], precision=HIGHEST) + p["b"]


def _xent(logits, y, fault):
    if fault == "half":
        logits, y = logits[:logits.shape[0] // 2], y[:y.shape[0] // 2]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------- Adam
def _adam(params, grads, m, v, step, lr, opt):
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    bc1 = 1.0 - b1 ** step
    bc2 = 1.0 - b2 ** step
    m = jax.tree.map(lambda m_, g: (b1 * m_ + (1 - b1) * g).astype(m_.dtype),
                     m, grads)
    v = jax.tree.map(
        lambda v_, g: (b2 * v_ + (1 - b2) * g * g).astype(v_.dtype), v, grads)
    params = jax.tree.map(
        lambda p, m_, v_: (p - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps))
                           ).astype(p.dtype),
        params, m, v)
    return params, m, v


def learning_rate(t: int, opt) -> float:
    """Cosine annealing from ``lr`` to ``min_lr`` over ``total_steps``
    rounds, no warm-up (Table II)."""
    frac = min(max(t / max(1.0, opt["total_steps"]), 0.0), 1.0)
    return opt["min_lr"] + 0.5 * (opt["lr"] - opt["min_lr"]) * (
        1.0 + math.cos(math.pi * frac))


# --------------------------------------------------------------------- steps
def _client_step(net, opt, fault, tr, st, m, v, step, x, y, lr):
    li = len(tr["layers"])

    def loss_fn(tr_):
        h, ns = features(tr_["layers"], st, x, net["strides"],
                         net["bn_momentum"], 0, li)
        return _xent(_head(tr_["out"], h), y, fault), (h, ns)

    (loss, (h, ns)), g = jax.value_and_grad(loss_fn, has_aux=True)(tr)
    tr, m, v = _adam(tr, g, m, v, step, lr, opt)
    return tr, ns, m, v, h, loss


def _server_step(net, opt, fault, li, tr, st, m, v, step, h, y, lr):
    def loss_fn(tr_):
        f, ns = features(tr_, st, h, net["strides"], net["bn_momentum"], li,
                         len(net["channels"]))
        return _xent(_head(tr_["head"], f), y, fault), ns

    (loss, ns), g = jax.value_and_grad(loss_fn, has_aux=True)(tr)
    tr, m, v = _adam(tr, g, m, v, step, lr, opt)
    return tr, ns, m, v, loss


def _aggregate(servers: List[dict]) -> List[dict]:
    """Eq. (1): each key's mean over the servers that hold it."""
    out = [{"trainable": dict(s["trainable"]), "state": dict(s["state"])}
           for s in servers]
    for part in ("trainable", "state"):
        keys = sorted({k for s in servers for k in s[part]})
        for k in keys:
            members = [i for i, s in enumerate(servers) if k in s[part]]
            if len(members) < 2:
                continue
            mean = jax.tree.map(lambda *xs: sum(xs) / len(xs),
                                *[servers[i][part][k] for i in members])
            for i in members:
                out[i][part][k] = mean
    return out


# ----------------------------------------------------------------------- run
def run_rounds(net: dict, job: dict, seed: int,
               batches: Sequence[Tuple[np.ndarray, np.ndarray]],
               rounds: int, dtype=jnp.float32,
               fault: Optional[str] = None) -> Dict:
    """Train ``rounds`` rounds from the seed's weights.

    ``net``: channels, strides, num_classes, bn_momentum.  ``job``:
    splits, strategy, local_epochs, aggregate_every and ``opt`` (lr,
    min_lr, total_steps, b1, b2, eps).  ``batches[i]``: client i's first
    ``rounds * local_epochs`` batches, ``([n, B, H, W, 3], [n, B])``.

    Returns the per-round mean client and server losses ``[rounds, 2]``,
    the final ``clients``/``client_m``/``servers``/``server_m`` trees, laid
    out as the program's ``TrainState`` holds them, and the same trees at
    round zero under ``initial``."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    splits, opt = list(job["splits"]), job["opt"]
    E, N = job["local_epochs"], len(splits)
    sequential = job["strategy"] == "sequential"
    lr_div = float(N) if sequential else 1.0
    cast = lambda t: jax.tree.map(lambda a: jnp.asarray(a, dtype), t)
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)

    with jax.default_matmul_precision("highest"):
        params, state = init_network(seed, net["channels"],
                                     net["num_classes"])
        clients = [cast(make_client(seed, params, state, net["channels"],
                                    net["num_classes"], li))
                   for li in splits]
        server_cuts = [min(splits)] if sequential else splits
        servers = [cast(make_server(params, state, len(net["channels"]), li))
                   for li in server_cuts]
        cm = [zeros(c["trainable"]) for c in clients]
        cv = [zeros(c["trainable"]) for c in clients]
        sm = [zeros(s["trainable"]) for s in servers]
        sv = [zeros(s["trainable"]) for s in servers]
        initial = jax.device_get({"clients": clients, "servers": servers,
                                  "client_m": cm, "server_m": sm})
        csteps = [0] * N
        ssteps = [0] * len(servers)
        cstep = jax.jit(partial(_client_step, net, opt, fault))
        sstep = {li: jax.jit(partial(_server_step, net, opt, fault, li))
                 for li in sorted(set(splits))}
        losses = []
        for r in range(rounds):
            lr = learning_rate(r, opt)
            lr_c, lr_s = jnp.asarray(lr, dtype), jnp.asarray(lr / lr_div,
                                                             dtype)
            closs, sloss = [], []
            for i, li in enumerate(splits):
                j = 0 if sequential else i
                for e in range(E):
                    x = jnp.asarray(batches[i][0][r * E + e], dtype)
                    y = np.array(batches[i][1][r * E + e])
                    if fault == "label":
                        y[0] = (y[0] + 1) % net["num_classes"]
                    y = jnp.asarray(y)
                    csteps[i] += 1
                    tr, st, cm[i], cv[i], h, cl = cstep(
                        clients[i]["trainable"], clients[i]["state"], cm[i],
                        cv[i], csteps[i], x, y, lr_c)
                    clients[i] = {"trainable": tr, "state": st}
                    ssteps[j] += 1
                    tr, st, sm[j], sv[j], sl = sstep[li](
                        servers[j]["trainable"], servers[j]["state"], sm[j],
                        sv[j], ssteps[j], lax.stop_gradient(h), y, lr_s)
                    servers[j] = {"trainable": tr, "state": st}
                    closs.append(cl)
                    sloss.append(sl)
            if (not sequential and fault != "noagg"
                    and (r + 1) % job["aggregate_every"] == 0):
                servers = _aggregate(servers)
            losses.append((np.mean([float(c) for c in closs]),
                           np.mean([float(s) for s in sloss])))
    return {"losses": np.asarray(losses), "initial": initial,
            "clients": jax.device_get(clients),
            "client_m": jax.device_get(cm),
            "servers": jax.device_get(servers),
            "server_m": jax.device_get(sm)}
