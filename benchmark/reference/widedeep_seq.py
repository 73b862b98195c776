"""Wide & Deep's logit (Cheng et al., arXiv:1606.07792, eq. 3), plainly.

``y = w_wide^T x + b + a_deep``: a linear model and a ReLU MLP over the
same input, summed.  The input is every slot's pooled value (a sequence
slot's keys are sum-pooled and CVM-transformed before, which is BASELINE
config 3's ``fused_seqpool_cvm``) and the dense features.  ``pooled`` is
[B, S, 3 + D].  The parameter tree is the program's
(``models/widedeep.py``): ``mlp`` a list of ``{w, b}``, ``wide_w``,
``wide_b``.  ``mm`` is the matrix product (``reference/step.py``).
"""

import jax.numpy as jnp


def logit(params, pooled, dense, mm=jnp.matmul):
    x = jnp.concatenate([pooled.reshape(pooled.shape[0], -1), dense], axis=1)
    wide = mm(x, params["wide_w"])[:, 0] + params["wide_b"][0]
    layers = params["mlp"]
    h = x
    for layer in layers[:-1]:
        h = jnp.maximum(mm(h, layer["w"]) + layer["b"], 0.0)
    return wide + (mm(h, layers[-1]["w"]) + layers[-1]["b"])[:, 0]
