"""BERT masked-language-model pre-training (``horovod_tpu.models.bert``).

``build(config, traffic)`` gives the harness the model, loss, optimizer and
seeded batch pool of one cell, and the work a step requires (computed from
shapes, so a share of peak cannot be moved by changing the program).
"""

import types


def build(config, traffic):
    import jax.numpy as jnp
    import numpy as np
    import optax
    from horovod_tpu.models.bert import Bert, BertConfig

    m, a = config["model"], config["assumed"]
    seq, batch = traffic["seq"], traffic["global_batch"]
    if seq > m["max_position_embeddings"]:
        raise SystemExit(f"chipbench: seq {seq} exceeds the model's positions")
    model = Bert(BertConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        hidden_dim=m["intermediate_size"],
        max_seq_len=m["max_position_embeddings"],
        norm_eps=m["layer_norm_eps"], dtype=jnp.dtype(a["compute_dtype"]),
        remat_policy=a["remat_policy"]))
    n_masked = max(1, round(seq * m["masked_lm_prob"]))

    def loss_fn(logits, labels):
        valid = labels >= 0
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.maximum(labels, 0))
        return (ce * valid).sum() / jnp.maximum(valid.sum(), 1)

    def pool(seed):
        """``pool_batches`` global batches: uniform random token ids, and in
        every sequence the same number of masked positions (BERT's
        ``max_predictions_per_seq``), whose labels are the "original"
        tokens; -1 marks a position that is not predicted."""
        rng = np.random.RandomState(seed)
        n = traffic["pool_batches"] * batch
        tokens = rng.randint(0, m["vocab_size"], (n, seq)).astype(np.int32)
        originals = rng.randint(0, m["vocab_size"], (n, seq)).astype(np.int32)
        picked = np.argsort(rng.rand(n, seq), axis=1)[:, :n_masked]
        labels = np.full((n, seq), -1, np.int32)
        np.put_along_axis(labels, picked,
                          np.take_along_axis(originals, picked, 1), 1)
        return tokens, labels

    opt = dict(config["optimizer"])
    return types.SimpleNamespace(
        model=model, loss_fn=loss_fn, pool=pool,
        optimizer=getattr(optax, opt.pop("name"))(**opt),
        flops_per_step=3 * batch * seq * forward_flops_per_token(m, seq),
        flash=flash_work(m, seq, batch) if seq >= 512 else None)


def forward_flops_per_token(m, seq):
    """Matrix-multiply operations the forward pass requires for one token
    (2 per multiply-add). The backward pass requires twice that again;
    rematerialised operations are not required and not counted."""
    d, ff, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    layer = 2 * (4 * d * d + 2 * d * ff) + 4 * seq * d     # q,k,v,o + ffn + QK^T,PV
    head = 2 * d * d + 2 * d * v                           # transform + tied logits
    return m["num_hidden_layers"] * layer + head


def flash_work(m, seq, batch):
    """Operations and bytes one training step requires of the attention
    kernels, over all layers: forward QK^T and PV (4 B H T^2 D), backward
    the score recomputation, dP, dV, dQ and dK (10 B H T^2 D); q, k, v, o
    read or written once forward (4 tensors) and q, k, v, o, dO, dQ, dK, dV
    once backward (8), in the compute type's 2 bytes."""
    bhtd = batch * seq * m["hidden_size"]
    return {"flops": m["num_hidden_layers"] * 14 * bhtd * seq,
            "bytes": m["num_hidden_layers"] * 12 * bhtd * 2}
