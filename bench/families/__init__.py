"""Model families: everything the benchmark does that depends on a
model's equations.

A configuration file (``bench/configs/<name>.json``) names its family
under ``"family"``; ``spec.family`` loads ``bench/families/<family>.py``
by path.  A family is added by adding its file: nothing else in
``bench/`` names one.  The module exports these names (``REQUIRED``);
``dims`` below is what ``dims(conf)`` returns, and every other name
takes it as it is:

``dims(conf) -> dict``
    The model's sizes from the configuration file's contents (its
    ``"config"`` holds the published keys as run), as a JSON-able dict.
    It has at least ``n_layers`` and ``vocab`` (the harness draws token
    ids below ``vocab``).
``program_config(conf, dims)``
    The program's ``repro.models.config.ModelConfig`` for it.
``make_params(dims, seed, dtype=jnp.bfloat16)``
    The program's parameter tree, made on the default device in one
    jitted call from the seed, in the served type, with
    ``weights.base_key`` and ``weights.leaf`` (every value exact in
    bfloat16 and float32).
``logits(dims, seed, tokens) -> np.ndarray``
    The plain float32 reference's ``(len(tokens), vocab)`` logits of one
    short sequence (tests).
``gaps(dims, seed, seqs, *, control) -> (sound, control)``
    ``seqs`` is a list of ``(tokens, n_prompt)``: a prompt followed by
    the tokens served for it.  ``sound`` holds, per sequence, a float32
    array with one row per served token: the reference's best logit at
    that position minus the reference's logit of the served token.
    ``control`` is None unless asked for; then it holds the same gap
    for the token that the control puts first: the same forward one
    precision step below the served bfloat16 (``precision.mm``'s
    float8).  The reference imports nothing of the program, makes its
    weights again from the seed and runs layer by layer (or in blocks)
    so that it fits the chip after the program's state is freed; where
    a configuration holds the chip's share of a layer (its experts, its
    heads), the reference computes the same share.
``decode_step(dims, ctx) -> (flops, bytes)``
    One decode token for each live row; ``ctx`` lists the keys each row
    attends.  The whole step: every layer and the head.
``decode_kernel(dims, ctx) -> (flops, bytes)``
    The paged decode attention kernel's calls in one such step.
``prefill(dims, n, start=0) -> (flops, bytes)``
    A prompt's ``n`` uncached tokens after ``start`` cached ones.
``flash_kernel(dims, n) -> (flops, bytes)``
    The flash prefill kernel's calls over one ``n``-token prompt; linear
    in the causal keys and in the tokens (``flash_kernel(dims, 1)`` is
    read as per key and per token).
``attention_layers(dims) -> int``
    The paged decode kernel's calls in one decode step.

The counts follow ``work.py``'s rules: a multiply-add is two
operations, bytes are the least a step must move through HBM, and only
the lengths actually computed count.
"""

REQUIRED = ("dims", "program_config", "make_params", "logits", "gaps",
            "decode_step", "decode_kernel", "prefill", "flash_kernel",
            "attention_layers")
