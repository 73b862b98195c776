"""DeepFM's logit (Guo et al., arXiv:1703.04247, eq. 1-4), plainly.

``y = y_FM + y_DNN``: the FM part is the first-order sum of each field's
scalar weight (the pulled ``embed_w``, plus a linear term over the dense
features) and the pairwise inner products of the fields' embedding
vectors, ``sum_{i<j} <v_i, v_j> = 1/2 sum_d ((sum_i v_id)^2 - sum_i
v_id^2)``; the deep part is a ReLU MLP over every field's pooled value
and the dense features.  ``pooled`` is [B, S, 3 + D]: CVM show, CVM
click, embed_w, embedx.  The parameter tree is the program's
(``models/deepfm.py``): ``mlp`` a list of ``{w, b}``, ``dense_w``,
``bias``.  ``mm`` is the matrix product (``reference/step.py``).
"""

import jax.numpy as jnp


def logit(params, pooled, dense, mm=jnp.matmul):
    first = (jnp.sum(pooled[:, :, 2], axis=1)
             + mm(dense, params["dense_w"])[:, 0])
    v = pooled[:, :, 3:]
    second = 0.5 * jnp.sum(jnp.sum(v, axis=1) ** 2
                           - jnp.sum(v * v, axis=1), axis=1)
    x = jnp.concatenate([pooled.reshape(pooled.shape[0], -1), dense], axis=1)
    layers = params["mlp"]
    for layer in layers[:-1]:
        x = jnp.maximum(mm(x, layer["w"]) + layer["b"], 0.0)
    deep = (mm(x, layers[-1]["w"]) + layers[-1]["b"])[:, 0]
    return params["bias"][0] + first + second + deep
