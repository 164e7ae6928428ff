"""Family-independent model facade.

The FL round engine, launchers and dry-runs consume this API only:

    m = build_model(cfg)
    params = m.init(key)
    loss, metrics = m.loss(params, batch)
    logits, cache = m.prefill(params, batch, cache_len=...)
    logits, cache = m.decode_step(params, cache, tokens)

Batch conventions:
* LM families (dense/moe/ssm/hybrid): {"tokens": [B,S] i32, "labels": [B,S]}
* vlm:    + {"patches": [B,P,D]}; logits cover patches+text, labels must be
  -1 (ignored) on the patch prefix.
* encdec: {"frames": [B,T_enc,D], "tokens": [B,S], "labels": [B,S]}
* cnn/mlp: {"images": [B,H,W,C], "labels": [B]}
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import cnn as cnn_mod
from repro.models import decoder as dec_mod
from repro.models import encdec as encdec_mod
from repro.models import mlp as mlp_mod
from repro.models.common import softmax_cross_entropy, token_accuracy

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    attn_impl: str = "auto"
    ssm_impl: str = "auto"
    sliding_window: Optional[int] = None   # long-context serving variant
    max_target_positions: int = 0          # encdec learned-pos extension
    moe_dropless: bool = False             # exact per-token routing
    scan_unroll: bool = False              # unroll layer scans (cost probes)
    moe_group_size: int = 0                # 0 = kernel default (512)
    cache_update: str = "dus"              # 'dus' (scatter) | 'onehot'
    ce_chunk: int = 0                      # >0: chunked cross-entropy

    @property
    def dtype(self):
        return _DTYPES[self.cfg.dtype]

    def for_training(self) -> "Model":
        """This model with ``auto`` kernels resolved for differentiation.

        No Pallas kernel here defines a backward pass, so every training
        step resolves ``auto`` attention and SSD scans to their XLA
        implementations, on every platform; explicit choices are kept.
        """
        return dataclasses.replace(
            self,
            attn_impl="xla" if self.attn_impl == "auto" else self.attn_impl,
            ssm_impl="xla" if self.ssm_impl == "auto" else self.ssm_impl)

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        if cfg.family == "cnn":
            return cnn_mod.init_cnn(cfg, key, self.dtype)
        if cfg.family == "mlp":
            return mlp_mod.init_mlp(cfg, key, self.dtype)
        if cfg.family == "encdec":
            return encdec_mod.init_encdec(
                cfg, key, self.dtype,
                max_target_positions=self.max_target_positions)
        return dec_mod.init_decoder(cfg, key, self.dtype)

    # --------------------------------------------------------------- forward
    def forward_train(self, params, batch, *, remat: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (logits, moe_aux)."""
        cfg = self.cfg
        if cfg.family == "cnn":
            return cnn_mod.cnn_forward(params, cfg, batch["images"]), \
                jnp.zeros((), jnp.float32)
        if cfg.family == "mlp":
            return mlp_mod.mlp_forward(params, cfg, batch["images"]), \
                jnp.zeros((), jnp.float32)
        if cfg.family == "encdec":
            enc = encdec_mod.encode(params, cfg, batch["frames"],
                                    attn_impl=self.attn_impl,
                                    unroll=self.scan_unroll)
            logits, aux, _ = encdec_mod.decode_full(
                params, cfg, batch["tokens"], enc, attn_impl=self.attn_impl,
                remat=remat, unroll=self.scan_unroll)
            return logits, aux
        logits, aux, _ = dec_mod.decoder_forward(
            params, cfg, batch["tokens"],
            prefix_embeds=batch.get("patches"),
            sliding_window=self.sliding_window, attn_impl=self.attn_impl,
            ssm_impl=self.ssm_impl, remat=remat,
            moe_dropless=self.moe_dropless, unroll=self.scan_unroll,
            moe_group_size=self.moe_group_size)
        return logits, aux

    # ------------------------------------------------------------------ loss
    def _chunked_ce(self, params, batch, *, remat: bool):
        """Sequence-chunked cross-entropy: the [B,S,V] fp32 logits tensor
        (tens of GB/device for 150k vocabs) is never materialised — the
        head matmul + softmax run per S-chunk inside a scan (§Perf C4)."""
        import jax
        from repro.models import decoder as dec_mod
        cfg = self.cfg
        hidden, aux, _ = dec_mod.decoder_forward(
            params, cfg, batch["tokens"],
            prefix_embeds=batch.get("patches"),
            sliding_window=self.sliding_window, attn_impl=self.attn_impl,
            ssm_impl=self.ssm_impl, remat=remat,
            moe_dropless=self.moe_dropless, unroll=self.scan_unroll,
            moe_group_size=self.moe_group_size, return_hidden=True)
        labels = batch["labels"]
        B, S, D = hidden.shape
        if labels.shape[1] != S:
            pad = S - labels.shape[1]
            labels = jnp.concatenate(
                [jnp.full((B, pad), -1, labels.dtype), labels], axis=1)
        head = params["embed"].T if cfg.tie_embeddings else             params["lm_head"]
        C = self.ce_chunk
        nc = S // C if S % C == 0 else 1
        C = S // nc
        hc = hidden.reshape(B, nc, C, D).transpose(1, 0, 2, 3)
        lc = labels.reshape(B, nc, C).transpose(1, 0, 2)

        def chunk(carry, xs):
            nll_sum, n_valid, n_correct = carry
            h, y = xs
            logits = (h @ head).astype(jnp.float32)
            valid = y != -1
            safe = jnp.where(valid, y, 0)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, safe[..., None],
                                       axis=-1)[..., 0]
            nll_sum += jnp.sum((logz - gold) * valid)
            n_valid += valid.sum()
            n_correct += ((jnp.argmax(logits, -1) == y) & valid).sum()
            return (nll_sum, n_valid, n_correct), None

        (nll_sum, n_valid, n_correct), _ = jax.lax.scan(
            chunk, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
                    jnp.zeros((), jnp.int32)), (hc, lc))
        nll = nll_sum / jnp.maximum(n_valid, 1)
        acc = n_correct / jnp.maximum(n_valid, 1)
        loss = nll + cfg.router_aux_coef * aux
        return loss, {"nll": nll, "accuracy": acc, "moe_aux": aux}

    def loss(self, params, batch, *, remat: bool = False
             ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        if self.ce_chunk and cfg.family not in ("cnn", "mlp", "encdec"):
            return self._chunked_ce(params, batch, remat=remat)
        logits, aux = self.forward_train(params, batch, remat=remat)
        labels = batch["labels"]
        if cfg.family in ("cnn", "mlp"):
            onehot_nll = softmax_cross_entropy(logits, labels)
            acc = token_accuracy(logits, labels)
            return onehot_nll, {"nll": onehot_nll, "accuracy": acc}
        if cfg.family == "vlm" and labels.shape[1] != logits.shape[1]:
            pad = logits.shape[1] - labels.shape[1]
            labels = jnp.concatenate(
                [jnp.full((labels.shape[0], pad), -1, labels.dtype), labels],
                axis=1)
        nll = softmax_cross_entropy(logits, labels)
        acc = token_accuracy(logits, labels)
        loss = nll + cfg.router_aux_coef * aux
        return loss, {"nll": nll, "accuracy": acc, "moe_aux": aux}

    # --------------------------------------------------------------- serving
    def prefill(self, params, batch, *, cache_len: int = 0
                ) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg
        if cfg.family in ("cnn", "mlp"):
            raise ValueError(f"{cfg.family} has no serving path")
        if cfg.family == "encdec":
            enc = encdec_mod.encode(params, cfg, batch["frames"],
                                    attn_impl=self.attn_impl,
                                    unroll=self.scan_unroll)
            logits, _, cache = encdec_mod.decode_full(
                params, cfg, batch["tokens"], enc, want_cache=True,
                cache_len=cache_len, attn_impl=self.attn_impl,
                unroll=self.scan_unroll)
            return logits, cache
        logits, _, cache = dec_mod.decoder_forward(
            params, cfg, batch["tokens"],
            prefix_embeds=batch.get("patches"), want_cache=True,
            cache_len=cache_len, sliding_window=self.sliding_window,
            attn_impl=self.attn_impl, ssm_impl=self.ssm_impl,
            moe_dropless=self.moe_dropless, unroll=self.scan_unroll,
            moe_group_size=self.moe_group_size)
        return logits, cache

    def decode_step(self, params, cache, tokens
                    ) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg
        if cfg.family == "encdec":
            return encdec_mod.decode_step(params, cfg, cache, tokens,
                                          attn_impl=self.attn_impl,
                                          unroll=self.scan_unroll,
                                          cache_update=self.cache_update)
        return dec_mod.decoder_decode_step(
            params, cfg, cache, tokens, sliding_window=self.sliding_window,
            attn_impl=self.attn_impl, unroll=self.scan_unroll,
            cache_update=self.cache_update)

    def make_cache(self, params, batch_size: int, capacity: int, *,
                   length: Optional[int] = None,
                   enc_states: Optional[jnp.ndarray] = None) -> Dict:
        cfg = self.cfg
        if cfg.family == "encdec":
            assert enc_states is not None
            cache = encdec_mod.make_empty_cache(
                cfg, batch_size, capacity, self.dtype, enc_states,
                length=length)
            # fill cross-attn K/V from the encoder states
            def per_layer(lp):
                from repro.models.attention import encode_memory_kv
                return encode_memory_kv(lp["cross_attn"], cfg, enc_states)
            xk, xv = jax.lax.map(per_layer, params["decoder"])
            cache["cross"] = {"k": xk, "v": xv}
            return cache
        return dec_mod.make_empty_cache(cfg, batch_size, capacity,
                                        self.dtype, length=length)

    def param_count(self, params=None) -> int:
        if params is None:
            return self.cfg.param_count()
        return sum(x.size for x in jax.tree_util.tree_leaves(params))


def build_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg=cfg, **kw)
