"""Knowledge distillation: the forward-KL step used to train speculative
draft models.

The one implementation of the step that makes
``speculative_generate`` actually fast: train a small student on the
teacher's next-token DISTRIBUTIONS (forward KL, teacher logits computed on
the fly — no logit dataset to stage), so the student's greedy/sampled
proposals match the teacher often enough for long accepted chunks.
``examples/draft_distill.py`` is the runnable story (acceptance
1.00 -> 4.00 of gamma=4).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax


def make_distill_step(teacher, student, optimizer):
    """Build the jitted forward-KL distillation step.

    Returns ``step(student_params, opt_state, batch, teacher_params) ->
    (student_params, opt_state, kl)``: the mean over positions of
    ``KL(teacher || student)`` up to the teacher-entropy constant (i.e.
    teacher-probability-weighted student cross-entropy), differentiated
    for the student only. ``teacher_params`` is a step ARGUMENT, not a
    closure — closing over it would bake the full teacher into the
    executable as a constant.

    Both models are applied as plain LMs (``apply({"params": ...},
    batch)`` -> ``[B, T, V]`` logits); softmaxes run in f32 whatever the
    models' compute dtypes.
    """

    @jax.jit
    def step(student_params, opt_state, batch, teacher_params):
        t_probs = jax.nn.softmax(
            teacher.apply({"params": teacher_params}, batch).astype(
                jnp.float32
            ),
            axis=-1,
        )

        def kl(sp):
            s_logp = jax.nn.log_softmax(
                student.apply({"params": sp}, batch).astype(jnp.float32),
                axis=-1,
            )
            return -jnp.mean(jnp.sum(t_probs * s_logp, axis=-1))

        loss, grads = jax.value_and_grad(kl)(student_params)
        updates, opt_state = optimizer.update(
            grads, opt_state, student_params
        )
        return optax.apply_updates(student_params, updates), opt_state, loss

    return step
