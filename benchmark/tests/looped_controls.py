"""The wrong-model controls of family ``looped_lm``: five programs that are not
the model, each of which the comparison with the plain reference must refuse
(the CPU tests at toy widths; the builder's chip run at the published ones).
Each is a context manager over ``(cfg, params)`` that gives what the program
under test is run with, and holds while that program is traced."""

import contextlib

import jax
import jax.numpy as jnp
from flax import linen as nn


def _is_layer_call(context) -> bool:
    return (context.method_name == "__call__" and context.module.name is not None
            and context.module.name.startswith("layer_"))


@contextlib.contextmanager
def three_passes_for_four(cfg, params):
    """The fourth application of every layer does nothing."""
    calls = {}

    def rule(next_fun, args, kwargs, context):
        if _is_layer_call(context):
            calls[context.module.name] = calls.get(context.module.name, 0) + 1
            if calls[context.module.name] % cfg["total_ut_steps"] == 0:
                return args[0]
        return next_fun(*args, **kwargs)

    with nn.intercept_methods(rule):
        yield cfg, params


@contextlib.contextmanager
def state_fed_on_unnormed(cfg, params):
    """Pass t + 1 reads what pass t's layers gave, not the final norm of it."""
    trunk = []

    def rule(next_fun, args, kwargs, context):
        if context.module.name == "final_norm":
            trunk.append(args[0])
        elif _is_layer_call(context) and context.module.name == "layer_0" and trunk:
            args = (trunk.pop(),) + args[1:]
        return next_fun(*args, **kwargs)

    with nn.intercept_methods(rule):
        yield cfg, params


@contextlib.contextmanager
def output_norms_dropped(cfg, params):
    """Attention's and the FFN's outputs join the stream un-normed."""
    def rule(next_fun, args, kwargs, context):
        if context.module.name in ("attn_out_norm", "ffn_out_norm"):
            return args[0]
        return next_fun(*args, **kwargs)

    with nn.intercept_methods(rule):
        yield cfg, params


@contextlib.contextmanager
def fp8_weights(cfg, params):
    """Every matrix rounded to float8 (e4m3) before the program reads it."""
    yield cfg, jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim > 1 else x, params)


@contextlib.contextmanager
def stay_and_leave_swapped(cfg, params):
    """1 - lambda where lambda is meant and lambda for 1 - lambda in the exit
    distribution: the gate's logit negated."""
    from bluefog_tpu.models import config_lm

    honest = config_lm.exit_distribution
    config_lm.exit_distribution = lambda gate_logits: honest(-gate_logits)
    try:
        yield cfg, params
    finally:
        config_lm.exit_distribution = honest


CONTROLS = (three_passes_for_four, state_fed_on_unnormed, output_norms_dropped, fp8_weights,
            stay_and_leave_swapped)


def under(control, family):
    """``family`` with ``system_logits`` run under ``control`` (None: as it is)."""
    import types

    if control is None:
        return family

    def system_logits(cfg, params, model_state, tokens):
        with control(cfg, params) as (c, p):
            return family.system_logits(c, p, model_state, tokens)

    return types.SimpleNamespace(plain_logits=family.plain_logits, system_logits=system_logits)
