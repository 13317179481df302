"""Plain float32 references, one module per model family.

Each module gives ``hidden(params, tokens, model, mm)``: the final-normed
hidden states of every position of one sequence, computed in float32
with no kernel, cache or batching, and ``head(params, model)``: the
``(d_model, vocab)`` output projection.  ``model`` is the ``"model"``
object of a configuration file; ``mm(a, b, spec)`` is the matrix product
(``jnp.einsum`` under ``jax.default_matmul_precision("highest")`` for the
reference, operands rounded to float8 for the control).  They import
nothing of the program under test.
"""
