"""Operations and bytes the work needs, from shapes alone.

``forward_flops`` counts the multiply-adds of every convolution and dense
product in one forward pass of the reference model (``bench/ref/models``)
for one sample, as 2 operations each, by walking the traced program: a
convolution costs 2 x (output elements) x (input channels per group) x
(kernel window), a dot product 2 x (output elements) x (contracted size).
Batch norm, activations and pooling are left out.

``round_flops`` is the useful work of one federated round of the FSFL
protocol (arXiv:2204.04424, Algorithm 1) for the whole cohort, with F the
forward operations per sample: the local epoch of the weights, 3F per
training sample (forward, and the gradients of weights and activations);
each of the E scale sub-epochs, 3F per training sample (the scale
gradient needs the weight and the data gradients); 1 + E validation
passes, F per validation sample; and the server's evaluation, F per test
image.  Recomputation does not count.
"""
from __future__ import annotations

import math

import jax
from jax.extend import core as jax_core
import numpy as np


def _eqn_flops(eqn) -> int:
    prim = eqn.primitive.name
    if prim == "conv_general_dilated":
        rhs = eqn.invars[1].aval.shape
        spec = eqn.params["dimension_numbers"].rhs_spec
        window = math.prod(rhs[d] for d in spec[2:])
        out = math.prod(eqn.outvars[0].aval.shape)
        return 2 * out * rhs[spec[1]] * window
    if prim == "dot_general":
        (lc, _), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        out = math.prod(eqn.outvars[0].aval.shape)
        return 2 * out * math.prod(lhs[d] for d in lc)
    return 0


def _jaxpr_flops(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        total += _eqn_flops(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, jax_core.ClosedJaxpr):
                    total += _jaxpr_flops(sub.jaxpr)
                elif isinstance(sub, jax_core.Jaxpr):
                    total += _jaxpr_flops(sub)
    return total


def forward_flops(init, apply, image_shape) -> int:
    """Operations of one forward pass of one sample of ``image_shape``
    (H, W, C)."""
    params, state = jax.eval_shape(init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1,) + tuple(image_shape), np.float32)
    jaxpr = jax.make_jaxpr(lambda p, s, x: apply(p, s, x, False))(
        params, state, x)
    return _jaxpr_flops(jaxpr.jaxpr)


def round_flops(forward: int, sizes: dict, clients: int, batch_size: int,
                subepochs: int) -> int:
    """Useful operations of one round (see the module docstring);
    ``sizes`` holds the per-client ``train`` and ``val`` rows and the
    ``test`` rows."""
    seen = (sizes["train"] // batch_size) * batch_size
    per_client = (3 * forward * seen * (1 + subepochs)
                  + forward * sizes["val"] * (1 + subepochs))
    return clients * per_client + forward * sizes["test"]


def peak(peaks: dict, device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind missing from the table is an
    error, never a default."""
    try:
        return peaks[device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} has no entry in "
                       "bench/peaks.json") from None
