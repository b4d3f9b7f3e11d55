"""`model_type: squid`: a decoder whose attention shares each KV head among
several query heads (4 over 2 here), with key names of its own. The program's
`TransformerLM` cannot express it, so the module is written here; the
`fedlora` kind trains it through `llm.federated_lora` as it does any module
whose kernels are named wq / wk / wv / wo."""
from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp


class Norm(nn.Module):
    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + 1e-6) * scale


class Block(nn.Module):
    heads: int
    kv_heads: int
    head: int
    ffn: int

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        dense = lambda n, name: nn.Dense(n, use_bias=False, name=name)
        with jax.named_scope("squid.gqa_attn"):
            h = Norm(name="norm_attn")(x)
            q = dense(self.heads * self.head, "wq")(h)
            k = dense(self.kv_heads * self.head, "wk")(h)
            v = dense(self.kv_heads * self.head, "wv")(h)
            q = q.reshape(b, t, self.kv_heads, self.heads // self.kv_heads,
                          self.head)
            k = k.reshape(b, t, self.kv_heads, self.head)
            v = v.reshape(b, t, self.kv_heads, self.head)
            s = jnp.einsum("bqkgd,bskd->bkgqs", q, k) * self.head ** -0.5
            causal = jnp.tril(jnp.ones((t, t), bool))
            p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
            o = jnp.einsum("bkgqs,bskd->bqkgd", p, v).reshape(b, t, -1)
            x = x + dense(d, "wo")(o)
        with jax.named_scope("squid.mlp"):
            h = Norm(name="norm_mlp")(x)
            x = x + dense(d, "w_down")(
                nn.silu(dense(self.ffn, "w_gate")(h))
                * dense(self.ffn, "w_up")(h))
        return x


class SquidLM(nn.Module):
    model: dict

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        m = self.model
        x = nn.Embed(m["vocab_size"], m["width"], name="embed")(tokens)
        for i in range(m["num_hidden_layers"]):
            x = Block(m["query_heads"], m["kv_heads"], m["head_width"],
                      m["ffn_width"], name=f"block_{i}")(x)
        x = Norm(name="final_norm")(x)
        return nn.Dense(m["vocab_size"], use_bias=False, name="lm_head")(x)


def build(model: dict, **options):
    """The module, and no replica spec: the program serves only its own
    `TransformerLM`, so this configuration has no `serve` cell. Of the
    path's options nothing applies: the flash kernel takes no grouped
    heads, and a model this small stores its activations."""
    return SquidLM({k: v for k, v in model.items()
                    if isinstance(v, int)}), None
