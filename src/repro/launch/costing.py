"""HLO cost reconstruction for scanned programs.

XLA's HloCostAnalysis visits each instruction ONCE — a lax.scan (while
loop) body is counted a single time regardless of trip count, so the full
step's ``cost_analysis()`` massively undercounts flops/bytes/collectives.
(Verified empirically: stablelm train_4k full-step flops == one layer x one
microbatch + embed/head + optimizer.)

Reconstruction: compile each *block* separately — with the SAME shardings,
remat policy and microbatch shapes as the real step — read its HLO cost,
and multiply by the true trip counts:

    train:   total = A * (emb + sum_i L_i * body_i) + opt
    serve:   total = head + sum_i L_i * body_i

where emb/head is recovered from the full step's (scan-once) cost by
subtracting each body counted the number of times it appears ONCE-PER-SCAN
in the traced program.  Block backward costs come from jax.vjp around the
jax.checkpoint'd block, so remat recompute IS included.  Collective wire
bytes are reconstructed with the same multipliers.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCell
from repro.launch import roofline as RF
from repro.launch.steps import _named, make_sharder, params_sds
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import serve as SV
from repro.models import ssm as ssm_mod
from repro.models.layers import gelu_mlp, swiglu
from repro.models.model import (PerfConfig, _dense_block, _mla_dense_block,
                                _moe_block, _norm, _remat, _shared_attn_block,
                                _ssm_block)
from repro.optim import AdamWConfig, adamw_init, adamw_update


def _cost_of(jitted, args) -> dict:
    compiled = jitted.lower(*args).compile()
    cost = compiled.cost_analysis()
    coll = RF.parse_collectives(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "wire": float(coll["total_wire_bytes"])}


def _zero():
    return {"flops": 0.0, "bytes": 0.0, "wire": 0.0}


def _add(a, b, k=1.0):
    return {key: a[key] + k * b[key] for key in a}


def _sub_clamped(a, b, k=1.0):
    return {key: max(a[key] - k * b[key], 0.0) for key in a}


def _layer_specs(pspecs_sub):
    """Drop the stacked-layer leading axis from a spec subtree."""
    return jax.tree_util.tree_map(
        lambda s: P(*s[1:]), pspecs_sub,
        is_leaf=lambda x: isinstance(x, P))


def _layer_sds(psds_sub):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), psds_sub)


class ComponentCoster:
    """Compiles per-block costs for one (cfg, cell, mesh) under a perf cfg."""

    def __init__(self, cfg: ArchConfig, cell: ShapeCell, mesh, perf: PerfConfig,
                 multi_pod: bool = False, dtype=jnp.bfloat16,
                 pspecs=None, psds=None):
        self.cfg = cfg
        self.cell = cell
        self.mesh = mesh
        self.perf = perf
        self.multi_pod = multi_pod
        self.dtype = dtype
        tiny = cell.kind != "train" and cell.global_batch < 16
        self.shd = make_sharder(mesh, multi_pod, tiny_batch=tiny,
                                parallelism=perf.parallelism)
        self.psds = psds if psds is not None else params_sds(cfg, dtype)
        from repro.parallel.sharding import param_specs
        self.pspecs = pspecs if pspecs is not None \
            else param_specs(cfg, self.psds, multi_pod)
        if cell.kind == "train":
            self.Bm = cell.global_batch // perf.accum_steps
        else:
            self.Bm = cell.global_batch
        self.S = cell.seq_len if cell.kind != "decode" else 1
        self.x_spec = P(self.shd.data_axes, None, None)
        self.x_sds = jax.ShapeDtypeStruct(
            (self.Bm, self.S, cfg.d_model), dtype)
        self.positions = None  # built lazily inside block fns

    # ---------------------------------------------------- train-block costs
    def _train_block_cost(self, block_fn: Callable, lp_sds, lp_specs,
                          has_aux: bool = False, extra_sds=(), extra_specs=()):
        cfg, shd, perf = self.cfg, self.shd, self.perf
        S = self.S

        def fwd(lp, x, *extra):
            import jax.numpy as jnp
            B = x.shape[0]
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            return block_fn(lp, x, positions, *extra)

        blk = _remat(fwd, perf.remat)

        def cost_fn(lp, x, *extra):
            y, pull = jax.vjp(blk, lp, x, *extra)
            if has_aux:
                ct = (y[0], jnp.ones((), jnp.float32))
            else:
                ct = y
            return pull(ct)

        jt = jax.jit(cost_fn, in_shardings=(
            _named(self.mesh, lp_specs),
            NamedSharding(self.mesh, self.x_spec),
            *[NamedSharding(self.mesh, s) for s in extra_specs]))
        return _cost_of(jt, (lp_sds, self.x_sds, *extra_sds))

    def _serve_block_cost(self, fn: Callable, in_specs, in_sds):
        jt = jax.jit(fn, in_shardings=in_specs)
        return _cost_of(jt, in_sds)

    def _opt_cost(self):
        ocfg = AdamWConfig(
            moments_dtype=jnp.bfloat16 if self.perf.opt_moments == "bf16"
            else jnp.float32)
        osds = jax.eval_shape(
            functools.partial(adamw_init, cfg=ocfg), self.psds)
        gsds = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), self.psds)
        ospecs = {"m": self.pspecs, "v": self.pspecs, "step": P()}

        def opt_fn(params, grads, opt):
            p, o, m = adamw_update(params, grads, opt, ocfg)
            return p, o
        jt = jax.jit(opt_fn, in_shardings=(
            _named(self.mesh, self.pspecs), _named(self.mesh, self.pspecs),
            _named(self.mesh, ospecs)))
        return _cost_of(jt, (self.psds, gsds, osds))

    # ---------------------------------------------------------- public API
    def bodies(self) -> dict[str, tuple[dict, int, int]]:
        """-> {name: (cost, count_in_traced_program, true_count_per_micro)}"""
        cfg = self.cfg
        shd, perf = self.shd, self.perf
        chunk = perf.attn_chunk
        out = {}
        if self.cell.kind == "train":
            mk = self._train_block_cost
            if cfg.family == "dense":
                lp_sds = _layer_sds(self.psds["layers"])
                lp_specs = _layer_specs(self.pspecs["layers"])
                fn = functools.partial(_dense_block, cfg=cfg, shd=shd,
                                       chunk=chunk)
                fn2 = lambda lp, x, pos: fn(lp, x, pos)
                out["block"] = (mk(fn2, lp_sds, lp_specs), 1, cfg.n_layers)
            elif cfg.family == "moe":
                nd = cfg.moe.first_dense
                dsds = _layer_sds(self.psds["dense_layers"])
                dspecs = _layer_specs(self.pspecs["dense_layers"])
                msds = _layer_sds(self.psds["layers"])
                mspecs = _layer_specs(self.pspecs["layers"])
                fd = functools.partial(_mla_dense_block, cfg=cfg, shd=shd,
                                       chunk=chunk)
                fm = functools.partial(_moe_block, cfg=cfg, shd=shd,
                                       chunk=chunk,
                                       groups=self.perf.moe_groups)
                out["dense_block"] = (
                    mk(lambda lp, x, pos: fd(lp, x, pos), dsds, dspecs),
                    1, nd)
                out["moe_block"] = (
                    mk(lambda lp, x, pos: fm(lp, x, pos), msds, mspecs,
                       has_aux=True), 1, cfg.n_layers - nd)
            elif cfg.family == "ssm":
                lp_sds = _layer_sds(self.psds["layers"])
                lp_specs = _layer_specs(self.pspecs["layers"])
                out["block"] = (
                    mk(lambda lp, x, pos: _ssm_block(lp, x, cfg, shd),
                       lp_sds, lp_specs), 1, cfg.n_layers)
            elif cfg.family == "hybrid":
                per = cfg.attn_every
                n_seg = max(cfg.n_layers // per, 1)
                n_scans = n_seg + (1 if cfg.n_layers % per else 0)
                lp_sds = _layer_sds(self.psds["layers"])
                lp_specs = _layer_specs(self.pspecs["layers"])
                sp_sds = self.psds["shared_block"]
                sp_specs = self.pspecs["shared_block"]
                fs = functools.partial(_shared_attn_block, cfg=cfg, shd=shd,
                                       chunk=chunk)
                out["shared_block"] = (
                    mk(lambda sp, x, pos: fs(sp, x, pos), sp_sds, sp_specs),
                    n_seg, n_seg)
                out["mamba_block"] = (
                    mk(lambda lp, x, pos: _ssm_block(lp, x, cfg, shd),
                       lp_sds, lp_specs), n_scans, cfg.n_layers)
            elif cfg.family == "encdec":
                from repro.models.model import _dec_block
                esds = _layer_sds(self.psds["enc_layers"])
                especs = _layer_specs(self.pspecs["enc_layers"])
                dsds = _layer_sds(self.psds["layers"])
                dspecs = _layer_specs(self.pspecs["layers"])
                enc_sds = jax.ShapeDtypeStruct(
                    (self.Bm, cfg.enc_seq, cfg.d_model), self.dtype)
                enc_spec = P(self.shd.data_axes, None, None)

                def enc_fn(lp, x, pos):
                    h = attn_mod.attn_train(
                        lp["attn"], _norm(x, lp["ln1"], cfg), pos, cfg, shd,
                        causal=False)
                    x = x + h
                    return x + gelu_mlp(lp["mlp"], _norm(x, lp["ln2"], cfg),
                                        shd)

                def dec_fn(lp, x, pos, enc_out):
                    import jax.numpy as jnp
                    F = enc_out.shape[1]
                    enc_pos = jnp.broadcast_to(
                        jnp.arange(F)[None], (x.shape[0], F))
                    return _dec_block(lp, x, enc_out, pos, enc_pos, cfg,
                                      shd, chunk)
                # encoder blocks see enc_seq-long x
                old_S, old_sds = self.S, self.x_sds
                self.S = cfg.enc_seq
                self.x_sds = enc_sds
                out["enc_block"] = (mk(enc_fn, esds, especs),
                                    1, cfg.n_enc_layers)
                self.S, self.x_sds = old_S, old_sds
                out["dec_block"] = (
                    mk(dec_fn, dsds, dspecs, extra_sds=(enc_sds,),
                       extra_specs=(enc_spec,)), 1, cfg.n_layers)
        else:
            out.update(self._serve_bodies())
        return out

    # ------------------------------------------------------- serve bodies
    def _serve_bodies(self):
        cfg, shd, perf, cell = self.cfg, self.shd, self.perf, self.cell
        B = cell.global_batch
        S = cell.seq_len
        decode = cell.kind == "decode"
        chunk = perf.attn_chunk
        out = {}
        csds_full = jax.eval_shape(
            functools.partial(SV.init_caches, cfg, B, S, self.dtype,
                              kv_quant=perf.kv_quant))
        from repro.launch.steps import _retarget_cache_specs
        from repro.parallel.sharding import cache_specs
        cspecs_full = _retarget_cache_specs(
            cache_specs(cfg, csds_full, self.multi_pod), shd)

        x_sds = jax.ShapeDtypeStruct((B, 1 if decode else S, cfg.d_model),
                                     self.dtype)
        x_spec = NamedSharding(self.mesh, P(shd.data_axes, None, None))
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
        pos_spec = NamedSharding(self.mesh, P())

        def attn_layer_fns(pkey, ckey, mla=False, with_moe=False,
                           with_mlp=True):
            lp_sds = _layer_sds(self.psds[pkey])
            lp_specs = _named(self.mesh, _layer_specs(self.pspecs[pkey]))
            c_sds = jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                csds_full[ckey])
            c_specs = _named(self.mesh, jax.tree_util.tree_map(
                lambda s: P(*s[1:]), cspecs_full[ckey],
                is_leaf=lambda x: isinstance(x, P)))

            if decode:
                def fn(lp, x, cache, pos):
                    if mla:
                        h, cache = mla_mod.mla_decode(
                            lp["attn"], _norm(x, lp["ln1"], cfg), cache,
                            pos, cfg, shd)
                    else:
                        h, cache = attn_mod.attn_decode(
                            lp["attn"], _norm(x, lp["ln1"], cfg), cache,
                            pos, cfg, shd)
                    x = x + h
                    if with_moe:
                        y, _ = moe_mod.moe_ffn(
                            lp["moe"], _norm(x, lp["ln2"], cfg), cfg, shd,
                            groups=self.perf.moe_groups)
                        x = x + y
                    elif with_mlp:
                        x = x + swiglu(lp["mlp"], _norm(x, lp["ln2"], cfg),
                                       shd)
                    return x, cache
                jt = jax.jit(fn, in_shardings=(lp_specs, x_spec, c_specs,
                                               pos_spec),
                             donate_argnums=(2,))
                return _cost_of(jt, (lp_sds, x_sds, c_sds, pos_sds))
            else:
                def fn(lp, x, cache):
                    import jax.numpy as jnp
                    positions = jnp.broadcast_to(
                        jnp.arange(S)[None], (B, S))
                    if mla:
                        h, cache = mla_mod.mla_prefill(
                            lp["attn"], _norm(x, lp["ln1"], cfg), positions,
                            cfg, shd, cache, chunk=chunk)
                    else:
                        h, cache = attn_mod.prefill_into_cache(
                            lp["attn"], _norm(x, lp["ln1"], cfg), positions,
                            cfg, shd, cache, chunk=chunk)
                    x = x + h
                    if with_moe:
                        y, _ = moe_mod.moe_ffn(
                            lp["moe"], _norm(x, lp["ln2"], cfg), cfg, shd,
                            groups=self.perf.moe_groups)
                        x = x + y
                    elif with_mlp:
                        x = x + swiglu(lp["mlp"], _norm(x, lp["ln2"], cfg),
                                       shd)
                    return x, cache
                jt = jax.jit(fn, in_shardings=(lp_specs, x_spec, c_specs),
                             donate_argnums=(2,))
                return _cost_of(jt, (lp_sds, x_sds, c_sds))

        if cfg.family == "dense":
            out["block"] = (attn_layer_fns("layers", "layers"),
                            1, cfg.n_layers)
        elif cfg.family == "moe":
            nd = cfg.moe.first_dense
            out["dense_block"] = (
                attn_layer_fns("dense_layers", "dense_layers", mla=True),
                1, nd)
            out["moe_block"] = (
                attn_layer_fns("layers", "layers", mla=True, with_moe=True),
                1, cfg.n_layers - nd)
        elif cfg.family in ("ssm", "hybrid"):
            lp_sds = _layer_sds(self.psds["layers"])
            lp_specs = _named(self.mesh, _layer_specs(self.pspecs["layers"]))
            st_sds = jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                csds_full["layers"])
            st_specs = _named(self.mesh, jax.tree_util.tree_map(
                lambda s: P(*s[1:]), cspecs_full["layers"],
                is_leaf=lambda x: isinstance(x, P)))
            if decode:
                def fn(lp, x, st):
                    h, st = ssm_mod.ssm_decode(
                        lp["ssm"], _norm(x, lp["ln"], cfg), st, cfg, shd)
                    return x + h, st
            else:
                def fn(lp, x, st):
                    from repro.models.serve import _ssm_prefill_block
                    return _ssm_prefill_block(lp, x, cfg, shd)
            jt = jax.jit(fn, in_shardings=(lp_specs, x_spec, st_specs),
                         donate_argnums=(2,))
            cost = _cost_of(jt, (lp_sds, x_sds, st_sds))
            if cfg.family == "ssm":
                out["block"] = (cost, 1, cfg.n_layers)
            else:
                per = cfg.attn_every
                n_seg = max(cfg.n_layers // per, 1)
                # python loops in serve: every layer traced individually
                out["mamba_block"] = (cost, cfg.n_layers, cfg.n_layers)
                sc_sds = jax.tree_util.tree_map(
                    lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                    csds_full["shared"])
                sc_specs = _named(self.mesh, jax.tree_util.tree_map(
                    lambda s: P(*s[1:]), cspecs_full["shared"],
                    is_leaf=lambda x: isinstance(x, P)))
                sp_specs = _named(self.mesh, self.pspecs["shared_block"])
                if decode:
                    def sfn(sp, x, cache, pos):
                        h, cache = attn_mod.attn_decode(
                            sp["attn"], _norm(x, sp["ln1"], cfg), cache,
                            pos, cfg, shd)
                        x = x + h
                        x = x + swiglu(sp["mlp"], _norm(x, sp["ln2"], cfg),
                                       shd)
                        return x, cache
                    jt = jax.jit(sfn, in_shardings=(
                        sp_specs, x_spec, sc_specs, pos_spec),
                                 donate_argnums=(2,))
                    scost = _cost_of(jt, (self.psds["shared_block"], x_sds,
                                          sc_sds, pos_sds))
                else:
                    def sfn(sp, x, cache):
                        import jax.numpy as jnp
                        positions = jnp.broadcast_to(
                            jnp.arange(S)[None], (B, S))
                        h, cache = attn_mod.prefill_into_cache(
                            sp["attn"], _norm(x, sp["ln1"], cfg), positions,
                            cfg, shd, cache, chunk=chunk)
                        x = x + h
                        x = x + swiglu(sp["mlp"], _norm(x, sp["ln2"], cfg),
                                       shd)
                        return x, cache
                    jt = jax.jit(sfn, in_shardings=(
                        sp_specs, x_spec, sc_specs),
                                 donate_argnums=(2,))
                    scost = _cost_of(jt, (self.psds["shared_block"], x_sds,
                                          sc_sds))
                out["shared_block"] = (scost, n_seg, n_seg)
        elif cfg.family == "encdec":
            # decoder self+cross blocks; encoder runs once at prefill
            out["block"] = (self._encdec_serve_block(
                csds_full, cspecs_full, x_sds, x_spec, pos_sds, pos_spec,
                decode), 1, cfg.n_layers)
            if not decode:
                out["enc_block"] = (self._encdec_encoder_block(),
                                    1, cfg.n_enc_layers)
        return out

    def _encdec_serve_block(self, csds_full, cspecs_full, x_sds, x_spec,
                            pos_sds, pos_spec, decode):
        cfg, shd = self.cfg, self.shd
        B, S = self.cell.global_batch, self.cell.seq_len
        dh = cfg.head_dim
        lp_sds = _layer_sds(self.psds["layers"])
        lp_specs = _named(self.mesh, _layer_specs(self.pspecs["layers"]))
        c_sds = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
            csds_full["layers"])
        c_specs = _named(self.mesh, jax.tree_util.tree_map(
            lambda s: P(*s[1:]), cspecs_full["layers"],
            is_leaf=lambda x: isinstance(x, P)))
        ck_sds = jax.ShapeDtypeStruct(
            (B, cfg.enc_seq, cfg.n_kv_heads, dh), self.dtype)
        ck_spec = NamedSharding(self.mesh, P(shd.data_axes, None, None, None))

        if decode:
            def fn(lp, x, cache, ck, cv, pos):
                import jax.numpy as jnp
                h, cache = attn_mod.attn_decode(
                    lp["self_attn"], _norm(x, lp["ln1"], cfg), cache, pos,
                    cfg, shd)
                x = x + h
                xq = _norm(x, lp["ln2"], cfg)
                hkv = cfg.n_kv_heads
                rep = cfg.n_heads // hkv
                q = (xq @ lp["cross_attn"]["wq"]).reshape(
                    B, 1, cfg.n_heads, dh)
                qf = q.astype(jnp.float32).reshape(B, hkv, rep, dh)
                s = jnp.einsum("bhrd,bkhd->bhrk", qf,
                               ck.astype(jnp.float32)) * dh ** -0.5
                p = jax.nn.softmax(s, axis=-1)
                o = jnp.einsum("bhrk,bkhd->bhrd", p, cv.astype(jnp.float32))
                o = o.reshape(B, 1, cfg.n_heads * dh).astype(x.dtype) \
                    @ lp["cross_attn"]["wo"]
                x = x + o
                x = x + gelu_mlp(lp["mlp"], _norm(x, lp["ln3"], cfg), shd)
                return x, cache
            jt = jax.jit(fn, in_shardings=(lp_specs, x_spec, c_specs,
                                           ck_spec, ck_spec, pos_spec),
                         donate_argnums=(2,))
            return _cost_of(jt, (lp_sds, x_sds, c_sds, ck_sds, ck_sds,
                                 pos_sds))
        else:
            from repro.models.model import _cross_attn, _dec_block
            enc_sds = jax.ShapeDtypeStruct(
                (B, cfg.enc_seq, cfg.d_model), self.dtype)
            enc_spec = NamedSharding(self.mesh,
                                     P(shd.data_axes, None, None))

            def fn(lp, x, cache, enc_out):
                import jax.numpy as jnp
                positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
                h, cache = attn_mod.prefill_into_cache(
                    lp["self_attn"], _norm(x, lp["ln1"], cfg), positions,
                    cfg, shd, cache, chunk=self.perf.attn_chunk)
                x = x + h
                xq = _norm(x, lp["ln2"], cfg)
                F = enc_out.shape[1]
                enc_pos = jnp.broadcast_to(jnp.arange(F)[None], (B, F))
                x = x + _cross_attn(lp["cross_attn"], xq, enc_out,
                                    positions, enc_pos, cfg, shd)
                x = x + gelu_mlp(lp["mlp"], _norm(x, lp["ln3"], cfg), shd)
                return x, cache
            jt = jax.jit(fn, in_shardings=(lp_specs, x_spec, c_specs,
                                           enc_spec),
                         donate_argnums=(2,))
            return _cost_of(jt, (lp_sds, x_sds, c_sds, enc_sds))

    def _encdec_encoder_block(self):
        cfg, shd = self.cfg, self.shd
        B = self.cell.global_batch
        lp_sds = _layer_sds(self.psds["enc_layers"])
        lp_specs = _named(self.mesh, _layer_specs(self.pspecs["enc_layers"]))
        x_sds = jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.d_model),
                                     self.dtype)
        x_spec = NamedSharding(self.mesh, P(shd.data_axes, None, None))

        def fn(lp, x):
            import jax.numpy as jnp
            pos = jnp.broadcast_to(jnp.arange(cfg.enc_seq)[None],
                                   (B, cfg.enc_seq))
            h = attn_mod.attn_train(lp["attn"], _norm(x, lp["ln1"], cfg),
                                    pos, cfg, shd, causal=False)
            x = x + h
            return x + gelu_mlp(lp["mlp"], _norm(x, lp["ln2"], cfg), shd)
        jt = jax.jit(fn, in_shardings=(lp_specs, x_spec))
        return _cost_of(jt, (lp_sds, x_sds))

    # ------------------------------------------------------ reconstruction
    def reconstruct(self, full_cost: dict, full_wire: float) -> dict:
        """full_cost: {'flops','bytes_accessed'} of the FULL step compile."""
        bodies = self.bodies()
        c_full = {"flops": full_cost["flops"],
                  "bytes": full_cost["bytes_accessed"],
                  "wire": full_wire}
        opt = self._opt_cost() if self.cell.kind == "train" else _zero()

        emb = dict(c_full)
        for name, (cost, n_traced, n_true) in bodies.items():
            # a fully-unrolled program traces every layer individually
            if not self.perf.scan_layers:
                n_traced = n_true
            emb = _sub_clamped(emb, cost, n_traced)
        emb = _sub_clamped(emb, opt)

        A = self.perf.accum_steps if self.cell.kind == "train" else 1
        total = _zero()
        total = _add(total, emb, A)
        for name, (cost, n_traced, n_true) in bodies.items():
            total = _add(total, cost, A * n_true)
        total = _add(total, opt)
        return {
            "total": total,
            "per_component": {
                name: {"cost": cost, "traced": n_traced, "true": n_true}
                for name, (cost, n_traced, n_true) in bodies.items()},
            "embed_head": emb,
            "optimizer": opt,
        }
