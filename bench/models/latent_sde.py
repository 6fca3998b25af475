"""The system under test for a ``latent_sde`` configuration: the
Latent-SDE ELBO training step of ``repro.launch.steps``."""

from __future__ import annotations

import jax

from bench.models import TrainProgram

#: ``repro.optim.adam``'s first-moment decay: after one step its first
#: moment holds ``(1 - B1)`` times the gradient the optimiser was given.
B1 = 0.9


def program_config(config: dict, traffic: dict, precision: str):
    from repro.core.sde import LatentSDEConfig

    return LatentSDEConfig(
        **config["model"], solver="reversible_heun",
        exact_adjoint=traffic["adjoint"] == "exact",
        use_pallas_kernels=traffic["use_pallas_kernels"], precision=precision)


def train_program(config: dict, traffic: dict, precision: str) -> TrainProgram:
    from repro.launch.steps import make_latent_sde_optimizer, make_latent_sde_step

    cfg = program_config(config, traffic, precision)
    opt_init, opt_update = make_latent_sde_optimizer(config["optimiser"]["lr"])
    step = jax.jit(make_latent_sde_step(cfg, opt_update, traffic["batch"],
                                        traffic["seq_len"],
                                        adjoint=traffic["adjoint"]))

    def call(state, key):
        params, opt, metrics = step(*state, key)
        return (params, opt), [metrics["loss"]]

    return TrainProgram(
        state=lambda params: (params, opt_init(params)),
        step=call,
        params=lambda state: state[0],
        first_grads=lambda state: jax.tree.map(lambda m: m / (1 - B1),
                                               state[1].m),
        unchanged=lambda state, key: (state, [jax.numpy.zeros(())]))
