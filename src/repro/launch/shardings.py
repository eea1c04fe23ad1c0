"""Parameter / batch / cache PartitionSpecs for the production meshes.

Policy (baseline; §Perf iterates on it):
  * tensor parallelism over "model": attention heads (or d_head when the
    head count doesn't divide the axis), FFN width, experts, mamba
    d_inner, vocab;
  * FSDP over "data": every parameter's largest remaining dim is sharded
    over the data axis when divisible (ZeRO-3-style; GSPMD inserts the
    all-gathers).  This is what lets the 76B arch + f32 optimizer moments
    fit 16 GB/chip;
  * batch over ("pod","data"); decode KV caches shard batch over "data"
    and kv-heads (or d_head) over "model"; for ``long_500k`` (batch=1)
    the cache's sequence axis shards over "data".

All helpers return specs with axis names filtered to the given mesh, so a
(1,1) host mesh yields fully-replicated specs and smoke tests run
unsharded.
"""
from __future__ import annotations

from typing import Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import model as MDL
from ..models.mamba import MambaState

BATCH = ("pod", "data")


def _axis(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _filter(mesh, spec: P) -> P:
    names = set(mesh.axis_names)
    out = []
    for e in spec:
        if e is None:
            out.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a in names)
            out.append(kept if kept else None)
        else:
            out.append(e if e in names else None)
    return P(*out)


def _param_spec(path: str, shape: Tuple[int, ...], mesh,
                fsdp: bool = True) -> P:
    """Baseline TP+FSDP spec for one parameter leaf."""
    m = _axis(mesh, "model")
    d = _axis(mesh, "data")
    entries: list = [None] * len(shape)

    # --- tensor-parallel dim ------------------------------------------------
    tp_dim = None
    if "embed" in path or "lm_head" in path:
        # vocab dim over model (embed: (V, D) dim0; lm_head: (D, V) dim1)
        tp_dim = 0 if "embed" in path else 1
    elif any(k in path for k in ("wq", "wk", "wv")):
        tp_dim = 1 if shape[1] % m == 0 else (
            2 if len(shape) > 2 and shape[2] % m == 0 else None)
    elif "wo" in path:
        tp_dim = 0 if shape[0] % m == 0 else (
            1 if shape[1] % m == 0 else None)
    elif any(k in path for k in ("wg", "wu", "wd", "router")) \
            and len(shape) == 3:
        tp_dim = 0                     # experts over model
    elif "router" in path:
        tp_dim = 1                     # (D, E)
    elif any(k in path for k in ("w_gate", "w_up")):
        tp_dim = 1                     # (D, F)
    elif "w_down" in path:
        tp_dim = 0                     # (F, D)
    elif "in_proj" in path or "x_proj" in path or "dt_proj" in path:
        tp_dim = 1                     # (D, k*d_inner)
    elif "out_proj" in path:
        tp_dim = 0                     # (d_inner, D)
    elif "a_log" in path and len(shape) == 2:
        tp_dim = 0                     # mamba1 a_log: (d_inner, N)
    elif any(k in path for k in ("a_log", "d_skip", "conv", "dt_bias",
                                 "norm_w")):
        # conv_w: (K, C) — channels over model; 1-D per-channel vectors
        tp_dim = len(shape) - 1
    if tp_dim is not None and shape[tp_dim] % m == 0 and m > 1:
        entries[tp_dim] = "model"
    else:
        tp_dim = None

    # --- FSDP dim over "data" -----------------------------------------------
    if fsdp and d > 1 and int(np.prod(shape)) >= (1 << 16):
        cands = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in cands:
            if i != tp_dim and entries[i] is None and shape[i] % d == 0 \
                    and shape[i] >= d:
                entries[i] = "data"
                break
    return _filter(mesh, P(*entries))


def param_specs(params, cfg, mesh, fsdp: bool = True):
    """Pytree of PartitionSpecs matching ``params``."""

    def spec_of(path, leaf):
        pstr = jax.tree_util.keystr(path)
        return _param_spec(pstr, tuple(np.shape(leaf)), mesh, fsdp=fsdp)

    return jax.tree_util.tree_map_with_path(spec_of, params)


def param_shardings(params, cfg, mesh, fsdp: bool = True):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, cfg, mesh, fsdp=fsdp))


def batch_spec(mesh) -> P:
    return _filter(mesh, P(BATCH))


def div_spec(mesh, shape: Tuple[int, ...], spec: P) -> P:
    """Drop spec entries whose mesh-axis product doesn't divide the dim."""
    out = []
    for dim, e in enumerate(_filter(mesh, spec)):
        if e is None:
            out.append(None)
            continue
        names = e if isinstance(e, tuple) else (e,)
        prod = int(np.prod([_axis(mesh, a) for a in names]))
        out.append(e if dim < len(shape) and shape[dim] % prod == 0
                   else None)
    return P(*out)


def batch_shardings(batch, mesh):
    def spec_of(leaf):
        shape = tuple(np.shape(leaf)) or getattr(leaf, "shape", ())
        nd = len(shape)
        spec = div_spec(mesh, shape, P(BATCH, *([None] * (nd - 1))))
        return NamedSharding(mesh, spec)
    return jax.tree.map(spec_of, batch)


def kv_cache_spec(cfg, batch: int, mesh, *, seq_shard: bool = False) -> P:
    """(B, T, KV, DH) cache spec.  seq_shard: shard T over "data"
    (sequence parallelism for batch=1 long-context)."""
    m = _axis(mesh, "model")
    d = _axis(mesh, "data")
    kv_e = "model" if cfg.n_kv_heads % m == 0 else None
    dh_e = "model" if (kv_e is None and cfg.d_head % m == 0) else None
    if seq_shard:
        return _filter(mesh, P(None, "data", kv_e, dh_e))
    b_e = BATCH if batch % (d * _axis(mesh, "pod")) == 0 else (
        "data" if batch % d == 0 else None)
    return _filter(mesh, P(b_e, None, kv_e, dh_e))


def mamba_state_spec(cfg, batch: int, mesh) -> "MambaState":
    """Specs for MambaState(conv (B,K-1,C), ssm (B,di,N)|(B,H,P,N))."""
    m = _axis(mesh, "model")
    d = _axis(mesh, "data")
    b_e = "data" if batch % d == 0 and d > 1 else None
    conv_c = cfg.d_inner + (2 * cfg.d_state if cfg.ssm_version == 2 else 0)
    conv = P(b_e, None, "model" if conv_c % m == 0 else None)
    if cfg.ssm_version == 2:
        nh = cfg.d_inner // cfg.head_dim
        ssm = P(b_e, "model" if nh % m == 0 else None, None, None)
    else:
        ssm = P(b_e, "model" if cfg.d_inner % m == 0 else None, None)
    return MambaState(conv=_filter(mesh, conv), ssm=_filter(mesh, ssm))


def decode_state_specs(cfg, batch: int, mesh, *, seq_shard: bool = False):
    """Spec pytree matching MDL.init_decode_state's structure."""
    kinds = MDL.layer_kinds(cfg)
    caches = []
    kv = kv_cache_spec(cfg, batch, mesh, seq_shard=seq_shard)
    for kind in kinds:
        if kind in ("attn", "moe_attn"):
            caches.append((kv, kv))
        elif kind == "mamba1":
            caches.append(mamba_state_spec(cfg, batch, mesh))
        elif kind == "mamba2+shared":
            caches.append((mamba_state_spec(cfg, batch, mesh), (kv, kv)))
        else:
            caches.append(mamba_state_spec(cfg, batch, mesh))
    return MDL.DecodeState(tuple(caches), P())


def tree_shardings(spec_tree, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def opt_state_specs(pspecs, mesh):
    """AdamW moments follow the parameter specs; step is replicated."""
    from ..train.optimizer import OptState
    return OptState(m=pspecs, v=pspecs, step=P())
