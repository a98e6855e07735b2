"""Pure-jnp oracle for the MaxSim late-interaction kernel."""

import jax
import jax.numpy as jnp


def maxsim_scores_ref(q, docs, doc_valid, q_valid=None):
    """q: (Lq, d); docs: (C, Ld, d); doc_valid: (C, Ld) bool;
    q_valid: optional (Lq,) bool → scores (C,) float32.

    score_c = Σ_{q tokens} max_{valid doc tokens} <q, d>.
    Fully-invalid docs score 0.
    """
    s = jnp.einsum("qd,cld->cql", q.astype(jnp.float32),
                   docs.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    s = jnp.where(doc_valid[:, None, :], s, -jnp.inf)
    per_q = jnp.max(s, axis=-1)                       # (C, Lq)
    per_q = jnp.where(jnp.isfinite(per_q), per_q, 0.0)
    if q_valid is not None:
        per_q = per_q * q_valid[None, :].astype(per_q.dtype)
    return jnp.sum(per_q, axis=-1)


def maxsim_scores_batch_ref(q, docs, doc_valid, q_valid=None):
    """Leading-batch-dim oracle: q (B, Lq, d); docs (B, C, Ld, d);
    doc_valid (B, C, Ld); q_valid optional (B, Lq) → (B, C) f32."""
    if q_valid is None:
        return jax.vmap(
            lambda a, b, c: maxsim_scores_ref(a, b, c))(q, docs, doc_valid)
    return jax.vmap(maxsim_scores_ref)(q, docs, doc_valid, q_valid)
