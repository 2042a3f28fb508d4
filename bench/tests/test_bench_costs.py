"""The yardstick's counts against counts taken from shapes: the GPT-2
FLOP function against the matrix products in the reference's own program,
and the top-k kernel's bytes against its operand and result."""
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core

from bench import harness
from bench.tests import tiny


def _dot_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            a = eqn.invars[0].aval.shape
            out = eqn.outvars[0].aval.shape
            total += 2.0 * math.prod(out) * math.prod(a[i] for i in lc)
        reps = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        for sub in _sub_jaxprs(eqn.params.values()):
            total += reps * _dot_flops(sub)
    return total


def _sub_jaxprs(values):
    for v in values:
        if isinstance(v, core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            yield from _sub_jaxprs(v)


def test_gpt2_flops_match_the_matrix_products():
    conf = dict(tiny.GPT2)
    _, mod = harness.load_config("gpt2-small-client")
    params = mod.init_params(conf, jax.random.PRNGKey(0))
    b, s = 2, 16
    batch = {"tokens": jnp.zeros((b, s), jnp.int32),
             "labels": jnp.zeros((b, s), jnp.int32)}
    loss = mod.reference_loss(conf)
    fwd = _dot_flops(jax.make_jaxpr(loss)(params, batch).jaxpr)
    both = _dot_flops(jax.make_jaxpr(jax.value_and_grad(loss))(
        params, batch).jaxpr)
    per_token = mod.model_flops_per_token(conf, s)
    assert both == 3 * fwd
    assert per_token * b * s == both


def test_topk_bytes_match_operand_and_result():
    from repro.kernels import ops
    metric = harness.load_metric("kernel.topk_rows_roofline")
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 300), jnp.float32)
    out = ops.topk_rows(x, 3.0, mode="jit")
    flops, nbytes = metric.kernel_cost(4, 300)
    assert nbytes == x.nbytes + out.nbytes
    assert flops == 25 * x.size
    assert int(np.sum(np.asarray(out) != 0)) > 0
