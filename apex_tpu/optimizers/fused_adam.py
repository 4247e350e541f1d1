"""FusedAdam / FusedAdamW — the ``multi_tensor_adam`` analog.

Behavioral spec: ``apex/optimizers/fused_adam.py`` (class ``:4``, ``step``
``:216-301``) over ``csrc/multi_tensor_adam.cu`` (``AdamFunctor:23-38``,
mode enum ``ADAM_MODE_0`` = L2 regularization into the gradient,
``ADAM_MODE_1`` = decoupled AdamW decay).  Points of parity:

- ``adam_w_mode=True`` (default) is AdamW: ``p -= lr*(update + wd*p)``;
  ``False`` folds ``wd*p`` into the gradient before the moments.
- ``bias_correction`` via ``1-beta^t`` exactly as ``fused_adam.py:241-247``.
- fp32 math for any param/grad dtype; optional fp32 masters in state
  (``master_weights=True``, ``fused_adam.py:71-104``).
- ``capturable`` mode (GPU-resident lr/step for CUDA graphs,
  ``fused_adam.py:128-214``) is meaningless under jit — every ``step`` is
  already a compiled program with traced ``lr``; the ``lr`` argument of
  :meth:`FusedAdam.step` provides the same capability.
- AMSGrad is rejected exactly like the reference (``fused_adam.py:80-81``).

The whole update is one XLA executable over the param pytree — the
multi-tensor fusion the CUDA kernel exists for comes from jit + donation
(see :func:`apex_tpu.optimizers.fused_step`).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.optimizers._common import (
    OptState,
    adam_apply,
    advance_step,
    apply_skip,
    f32,
    finalize_params,
    resolve_master,
    scale_grads,
    tree_f32,
    tree_map_flat,
    tree_map_multi,
    tree_zeros_f32,
)

__all__ = ["FusedAdam"]


class FusedAdam:
    """Adam/AdamW with the Apex constructor surface
    (``apex/optimizers/fused_adam.py:4-70``)."""

    def __init__(
        self,
        lr: float = 1e-3,
        bias_correction: bool = True,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-8,
        adam_w_mode: bool = True,
        weight_decay: float = 0.0,
        amsgrad: bool = False,
        master_weights: bool = False,
        flat: bool = False,
    ):
        if amsgrad:
            raise RuntimeError(
                "FusedAdam does not support the AMSGrad variant "
                "(parity with apex/optimizers/fused_adam.py:80)"
            )
        self.lr = lr
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay
        self.master_weights = master_weights
        # flat=True applies the purely elementwise update over one chunked
        # buffer instead of per-leaf (equal to ~1 ulp of fma contraction) — one wide
        # kernel per op vs one small kernel per tensor, at the cost of a
        # pack/unpack copy.  Which side wins depends on how fragmented
        # the tree is; not measured on the chip (ROADMAP W11, D5).
        self.flat = flat

    def init(self, params) -> OptState:
        return OptState(
            step=jnp.int32(0),
            slots={
                "exp_avg": tree_zeros_f32(params),
                "exp_avg_sq": tree_zeros_f32(params),
            },
            master=tree_f32(params) if self.master_weights else None,
        )

    def step(
        self,
        grads,
        state: OptState,
        params,
        *,
        lr=None,
        grad_scale=None,
        skip_update=None,
    ):
        lr = f32(self.lr if lr is None else lr)
        b1, b2, eps, wd = self.beta1, self.beta2, self.eps, self.weight_decay
        t = state.step + 1
        g = scale_grads(grads, grad_scale)
        p32 = resolve_master(params, state.master, self.master_weights)

        if self.bias_correction:
            # identical correction factors to fused_adam.py:241-247
            bc1 = 1.0 - b1 ** f32(t)
            bc2 = 1.0 - b2 ** f32(t)
        else:
            bc1 = bc2 = jnp.float32(1.0)

        def leaf(p, g, m, v):
            return adam_apply(p, g, m, v, lr=lr, b1=b1, b2=b2, eps=eps,
                              wd=wd, bc1=bc1, bc2=bc2,
                              adam_w_mode=self.adam_w_mode)

        tmap = tree_map_flat if self.flat else tree_map_multi
        new_p32, new_m, new_v = tmap(
            leaf, 3, p32, g, state.slots["exp_avg"], state.slots["exp_avg_sq"]
        )

        new_p32 = apply_skip(skip_update, new_p32, p32)
        new_m = apply_skip(skip_update, new_m, state.slots["exp_avg"])
        new_v = apply_skip(skip_update, new_v, state.slots["exp_avg_sq"])

        new_params = finalize_params(new_p32, params, self.master_weights)
        new_state = OptState(
            step=advance_step(state.step, skip_update),
            slots={"exp_avg": new_m, "exp_avg_sq": new_v},
            master=new_p32 if self.master_weights else None,
        )
        return new_params, new_state
