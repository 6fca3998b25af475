"""Model FLOPs of one Latent-SDE ELBO training step, from the shapes.

Counted: the matrix products of the field MLPs (posterior drift ``nu``,
prior drift ``mu``, diffusion ``sigma``; one evaluation of each per solver
point, the reversible Heun method's one per step plus the initial one), of
the GRU encoder, of the ``qz0`` and ``zeta`` heads, and of the readout at
the observation times: 2 operations per multiply-add.  The backward pass
counts as twice the forward; the reversible adjoint's reconstruction of
the forward is recomputation and is not counted.
"""

from __future__ import annotations


def mlp(sizes) -> int:
    """Multiply-add operations x 2 of one row through an MLP."""
    return sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))


def forward_flops_per_row(config: dict, traffic: dict) -> int:
    d = config["model"]
    x, c, v, y = d["hidden_dim"], d["context_dim"], d["initial_noise_dim"], \
        d["data_dim"]
    hid = [d["width"]] * d["depth"]
    points = d["num_steps"] + 1
    obs = traffic["seq_len"]
    gru = obs * (2 * y * 3 * c + 2 * c * 3 * c)
    heads = mlp([c] + hid + [2 * v]) + mlp([v] + hid + [x]) + obs * 2 * x * y
    fields = points * (mlp([1 + x + c] + hid + [x]) + 2 * mlp([1 + x] + hid + [x]))
    return gru + heads + fields


def model_flops_per_step(config: dict, traffic: dict) -> int:
    return 3 * traffic["batch"] * forward_flops_per_row(config, traffic)
