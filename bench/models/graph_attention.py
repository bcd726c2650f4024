"""Model kind ``graph_attention``: the GAT of DGL's ogbn-arxiv example
(``examples/pytorch/ogb/ogbn-arxiv/models.py``, class ``GAT``; Veličković et
al., arXiv:1710.10903) in inference mode. Layer ``l`` with ``heads`` heads
of width ``F``:

* ``z = h W_l`` as ``[N, heads, F]``, ``el = sum_f z a_l``, ``er = sum_f z
  a_r``;
* one engine request per layer, ``agg = submit(gid, z, attention=(el, er),
  negative_slope=...)``: ``agg_i = sum_j softmax_j(LeakyReLU(el_j + er_i))
  z_j`` over row i's nonzeros;
* ``out = agg + (h R_l)``, the residual linear shaped the same way;
* layers before the last: heads flattened, BatchNorm (eval), ReLU; the last:
  mean over the heads plus a bias, the logits ``[N, classes]``.

The dense parts run on the device between the requests. The plain reference
is ``bench/references/gat.py``.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.data import CSR
from bench.references import gat as reference
from bench.system import jax_seed
from bench.work import F32, Work, dense_work

_HIGHEST = jax.lax.Precision.HIGHEST


def prepare(g: CSR) -> CSR:
    """The graph made bidirected, without repeated edges, with one self
    loop per node, as the DGL example prepares ogbn-arxiv
    (``to_bidirected``, then ``remove_self_loop().add_self_loop()``); every
    value 1, which attention does not read."""
    rowptr, colidx, _ = g
    n = len(rowptr) - 1
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(rowptr))
    dst = np.asarray(colidx, np.int64)
    loops = np.arange(n, dtype=np.int64)
    off = src != dst
    key = np.unique(np.concatenate([src[off] * n + dst[off],
                                    dst[off] * n + src[off],
                                    loops * n + loops]))
    rows, cols = np.divmod(key, n)
    new_rowptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=new_rowptr[1:])
    return new_rowptr, cols, np.ones(len(cols), np.float32)


def _shape(config: Dict):
    m = config["model"]
    heads, layers = int(m["heads"]), int(m["layers"])
    ins = [int(m["in"])] + [heads * int(m["hidden"])] * (layers - 1)
    outs = [int(m["hidden"])] * (layers - 1) + [int(m["classes"])]
    return heads, list(zip(ins, outs))


def make_inputs(config: Dict, n: int, sets: int, seed: int):
    """``sets`` feature matrices ``[sets, n, in]``, N(0, 1), and the
    parameters, in one jitted call from the seed: ``fc``, ``res_fc``,
    ``attn_l`` and ``attn_r`` Xavier-normal with the ReLU gain sqrt(2), as
    the DGL example initialises them; BatchNorm running mean N(0, 0.1^2),
    running variance, scale U(0.5, 1.5), shift N(0, 0.1^2); the last bias
    N(0, 0.1^2). The parameters also carry the configuration's LeakyReLU
    ``negative_slope``."""
    heads, dims = _shape(config)
    gain = np.sqrt(2.0)

    @jax.jit
    def make(key):
        kx, kb, *kl = jax.random.split(key, 2 + len(dims))
        xs = jax.random.normal(kx, (sets, n, dims[0][0]), jnp.float32)
        layers = []
        for i, (k, (fi, f)) in enumerate(zip(kl, dims)):
            kw, kr, ka, kc, km, kv, ks, kt = jax.random.split(k, 8)
            fo = heads * f
            std_w = gain * np.sqrt(2.0 / (fi + fo))
            # torch's fans of a (1, heads, F) parameter: heads * F and F
            std_a = gain * np.sqrt(2.0 / (heads * f + f))
            layer = {
                "w": std_w * jax.random.normal(kw, (fi, fo), jnp.float32),
                "res": std_w * jax.random.normal(kr, (fi, fo), jnp.float32),
                "a_l": std_a * jax.random.normal(ka, (heads, f), jnp.float32),
                "a_r": std_a * jax.random.normal(kc, (heads, f), jnp.float32),
            }
            if i < len(dims) - 1:
                layer["bn"] = {
                    "mean": 0.1 * jax.random.normal(km, (fo,), jnp.float32),
                    "var": jax.random.uniform(kv, (fo,), jnp.float32,
                                              0.5, 1.5),
                    "scale": jax.random.uniform(ks, (fo,), jnp.float32,
                                                0.5, 1.5),
                    "shift": 0.1 * jax.random.normal(kt, (fo,), jnp.float32),
                }
            layers.append(layer)
        bias = 0.1 * jax.random.normal(kb, (dims[-1][1],), jnp.float32)
        return xs, {"layers": layers, "bias": bias}

    xs, params = make(jax.random.key(jax_seed(seed, 2)))
    params["negative_slope"] = float(config["model"]["negative_slope"])
    return xs, params


@jax.jit
def _project(h, layer):
    """``z``, ``el``, ``er`` and the residual of one layer."""
    heads, f = layer["a_l"].shape
    z = jnp.dot(h, layer["w"], precision=_HIGHEST).reshape(-1, heads, f)
    res = jnp.dot(h, layer["res"], precision=_HIGHEST).reshape(-1, heads, f)
    return (z, jnp.sum(z * layer["a_l"], axis=-1),
            jnp.sum(z * layer["a_r"], axis=-1), res)


@jax.jit
def _norm_relu(agg, res, bn):
    h = (agg + res).reshape(agg.shape[0], -1)
    h = ((h - bn["mean"]) / jnp.sqrt(bn["var"] + reference.BN_EPS)
         * bn["scale"] + bn["shift"])
    return jax.nn.relu(h)


@jax.jit
def _logits(agg, res, bias):
    return jnp.mean(agg + res, axis=1) + bias


def forward(engine, graph_id: str, params: Dict, x) -> jax.Array:
    """One pass: per layer the projections on the device, one attention
    request through the engine, then the residual, BatchNorm and ReLU (or,
    last, the mean over heads and the bias)."""
    h = x
    layers = params["layers"]
    for i, layer in enumerate(layers):
        with jax.profiler.TraceAnnotation("bench.dense"):
            z, el, er, res = _project(h, layer)
        with jax.profiler.TraceAnnotation("bench.aggregate"):
            agg = engine.submit(
                graph_id, z, attention=(el, er),
                negative_slope=params["negative_slope"]).result()
        with jax.profiler.TraceAnnotation("bench.dense"):
            h = (_norm_relu(agg, res, layer["bn"]) if i < len(layers) - 1
                 else _logits(agg, res, params["bias"]))
    return jax.block_until_ready(h)


def attention_work(nnz: int, n: int, heads: int, f_head: int) -> Work:
    """Compulsory work of one attention aggregation over a square CSR graph
    of ``n`` rows and ``nnz`` nonzeros, ``heads`` heads of ``f_head``:

      flops = nnz * heads * (2 * f_head + 4)   (score add, LeakyReLU, exp,
              and the division per edge and head; a multiply and an add per
              feature)
      bytes = nnz * 4 + (n + 1) * 4            (the CSR structure)
              + 2 * n * heads * 4              (el and er read once)
              + 2 * n * heads * f_head * 4     (z read once, out written once)
    """
    return Work(flops=float(nnz * heads * (2 * f_head + 4)),
                bytes=float(nnz * 4 + (n + 1) * 4 + 2 * n * heads * F32
                            + 2 * n * heads * f_head * F32))


def work(graph: CSR, config: Dict) -> Dict[str, Work]:
    """Compulsory work of one pass: its attention aggregations, and its dense
    parts (``fc`` and ``res_fc``, and the two score projections, each an
    ``[N * heads, F] x [F, 1]`` product)."""
    n, nnz = len(graph[0]) - 1, len(graph[1])
    heads, dims = _shape(config)
    attention = dense = Work()
    for fi, f in dims:
        attention = attention + attention_work(nnz, n, heads, f)
        dense = (dense + dense_work(n, fi, heads * f).scaled(2)
                 + dense_work(n * heads, f, 1).scaled(2))
    return {"attention": attention, "dense": dense}


def reference_pairs(graph: CSR, params: Dict, x, precision: str):
    """``(ref, terms)``: the plain forward pass's logits, and the number of
    terms each row of the last aggregation sums (its degree, self loop
    included)."""
    ref = reference.gat_forward(graph, x, params, params["negative_slope"],
                                precision)
    return np.asarray(ref), np.diff(graph[0])


def tiny(config: Dict) -> Dict:
    """The configuration at a size a CPU test holds: 3,000 nodes, 16 input
    features, 2 heads of 20 (not a lane multiple), 8 classes."""
    graph = dict(config["graph"], nodes=3000, edges=20000, max_degree=300)
    return dict(config, graph=graph, model=dict(
        config["model"], **{"in": 16, "hidden": 20, "heads": 2,
                            "classes": 8}))
