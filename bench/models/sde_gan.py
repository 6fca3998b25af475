"""The system under test for an ``sde_gan`` configuration: the SDE-GAN
training step with careful clipping, and the generator behind the
continuous-batching trajectory server."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.models import TrainProgram

#: ``repro.optim.adadelta``'s decay: after one step its squared-gradient
#: accumulator holds ``(1 - RHO) * g**2``.
RHO = 0.9


def program_config(config: dict, precision: str):
    from repro.core.sde import NeuralSDEConfig

    return NeuralSDEConfig(**config["model"], solver="reversible_heun",
                           exact_adjoint=True, use_pallas_kernels=False,
                           precision=precision)


def _grads_from_adadelta(state):
    # |g| elementwise; the sign is lost, the leaf norm is not
    return jax.tree.map(lambda a: jnp.sqrt(a / (1 - RHO)), state.m)


def train_program(config: dict, traffic: dict, precision: str) -> TrainProgram:
    from repro.launch.steps import make_gan_optimizers, make_sde_gan_step

    cfg = program_config(config, precision)
    (g_init, g_update), (d_init, d_update) = make_gan_optimizers(
        lr=config["optimiser"]["lr"], constraint="clip")
    step = jax.jit(make_sde_gan_step(cfg, g_update, d_update, traffic["batch"],
                                     traffic["seq_len"], constraint="clip"))

    def call(state, key):
        params, g_state, d_state, metrics = step(*state, key)
        return (params, g_state, d_state), [metrics["gen_loss"],
                                            metrics["disc_loss"]]

    return TrainProgram(
        state=lambda params: (params, g_init(params["gen"]),
                              d_init(params["disc"])),
        step=call,
        params=lambda state: state[0],
        first_grads=lambda state: {"gen": _grads_from_adadelta(state[1]),
                                   "disc": _grads_from_adadelta(state[2][0])},
        unchanged=lambda state, key: (state, [jnp.zeros(()), jnp.zeros(())]))


def serving_model(config: dict, gen_params, precision: str):
    """The generator as a registry entry, as a trainer's bundle loads."""
    from repro.serving import LoadedModel

    return LoadedModel(model_id="default", workload="sde-gan",
                       cfg=program_config(config, precision),
                       params=gen_params)
