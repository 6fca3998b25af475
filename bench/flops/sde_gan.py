"""Model FLOPs of one SDE-GAN training step, from the shapes.

Counted: the matrix products of the generator's fields (``mu``, ``sigma``)
and the discriminator's (``f``, ``g``) at every solver point (one
evaluation per reversible Heun step plus the initial one), once over the
joint solve of the generated paths and once, for ``f`` and ``g``, over the
real paths; the heads ``zeta``, ``xi`` (both paths), the readout ``m``
(both) and ``ell`` (the generated initial value): 2 operations per
multiply-add.  The contractions that couple generator and discriminator
inside the joint fields are a few per cent of the fields and are left
out.  The backward pass counts as twice the forward.
"""

from __future__ import annotations


def mlp(sizes) -> int:
    return sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))


def forward_flops_per_row(config: dict, traffic: dict) -> int:
    d = config["model"]
    X, W, V, Y = d["hidden_dim"], d["noise_dim"], d["initial_noise_dim"], \
        d["data_dim"]
    H = d["disc_hidden_dim"]
    hid = [d["width"]] * d["depth"]
    dhid = [d["disc_width"]] * d["disc_depth"]
    gen_points = d["num_steps"] + 1
    real_points = traffic["seq_len"]
    gen_fields = mlp([1 + X] + hid + [X]) + mlp([1 + X] + hid + [X * W])
    disc_fields = mlp([1 + H] + dhid + [H]) + mlp([1 + H] + dhid + [H * (1 + Y)])
    heads = (mlp([V] + hid + [X]) + 2 * X * Y + 2 * mlp([1 + Y] + dhid + [H])
             + 2 * 2 * H)
    return (gen_points * (gen_fields + disc_fields)
            + real_points * disc_fields + heads)


def model_flops_per_step(config: dict, traffic: dict) -> int:
    return 3 * traffic["batch"] * forward_flops_per_row(config, traffic)
