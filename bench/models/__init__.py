"""Model modules of the benchmark, one per family of backbone architectures.

A configuration file (``bench/configs/<config>.json``) names its family in
the key ``"family"``; ``bench.spec`` loads ``bench/models/<family>.py`` by
path and hands it to the kinds, ``bench/aot.py`` and the metric readers as
``Cell.model`` (``ctx["model"]`` in a reader). None of them names a family.
A new architecture is a new file here: it is loaded by path, so it imports
what it shares absolutely (``from bench import flops``, ``from
bench.models.common import ...``), and imports nothing of the program.

What a family module provides (``dense.py`` is the model to copy):

``Sizes``, ``sizes(config) -> Sizes``
    A frozen dataclass of the sizes the configuration's ``run`` section
    gives, with at least ``d``, ``layers``, ``vocab``, ``frontend`` (0
    without an image frontend), ``image_patches``, ``rank``, ``alpha``,
    ``modalities`` (the adapted ones) and ``scale`` (alpha / rank).
``backbone_weights(seed, sz, dtype, sharding=None)``
    The frozen backbone in the program's layout, made on the device in one
    jitted call from the seed, in the type it is served in.
``backbone_shapes(sz, dtype)``
    The same tree as ``jax.ShapeDtypeStruct``s, with nothing made.
``Reference(seed, sz, quant=None)``
    The plain float32 reference over the same weights, made again from the
    seed; ``quant="fp8"`` rounds its weights to float8 (the control). Its
    methods, with client trees stacked over K on their leading axis:
    ``loss_and_grads(adapters_k, tokens, labels, mask, patches)`` ->
    (per-client mean masked loss (K,), adapter gradients);
    ``embed(adapters_k, tokens, patches)`` -> (K*B, S, D) embeddings;
    ``hidden(x0)`` -> final-normed hidden states; ``logits_at(h, pos)`` ->
    (N, P, V) logits at positions ``pos`` (N, P).
The counts, by the rules of ``bench/flops.py`` and built from its terms:
    ``round_flops(sz, *, sequences, text_len, image_len, loss_positions)``;
    ``prefill_cost(sz, length)`` and ``decode_cost(sz, positions)``, each
    (FLOPs, bytes); ``weight_bytes(sz)``; ``kv_bytes_per_position(sz)``;
    ``flash_launch_cost(sz, out_dims, seq)``, the (FLOPs, bytes) of one
    flash-attention launch whose output has dims ``out_dims``.

FedNano's own pieces, the same under every backbone (the seed's key, the
NanoAdapters, the clients' AdamW and the Fisher merge), are in
``common.py``, which is not a family.
"""
