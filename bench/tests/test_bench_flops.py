"""Model FLOPs and Pallas bytes per step against counts made by hand at a
tiny size."""

import jax

from bench.flops import latent_sde, pallas, sde_gan

LATENT = {"model": {"hidden_dim": 1, "context_dim": 2, "initial_noise_dim": 1,
                    "data_dim": 2, "width": 3, "depth": 1, "num_steps": 2}}
GAN = {"model": {"hidden_dim": 1, "noise_dim": 1, "initial_noise_dim": 1,
                 "data_dim": 1, "disc_hidden_dim": 1, "width": 2, "depth": 1,
                 "disc_width": 2, "disc_depth": 1, "num_steps": 2}}


def test_latent_model_flops_by_hand():
    # GRU, 3 observations: 3 * (2*2*6 + 2*2*6) = 144
    # qz0 2-3-2: 12 + 12; zeta 1-3-1: 6 + 6; readout 3 * 2*1*2 = 12 -> 48
    # 3 solver points of nu 4-3-1 (24 + 6), mu and sigma 2-3-1 (12 + 6): 198
    per_row = 144 + 48 + 198
    assert latent_sde.forward_flops_per_row(LATENT, {"seq_len": 3}) == per_row
    assert latent_sde.model_flops_per_step(
        LATENT, {"seq_len": 3, "batch": 5}) == 3 * 5 * per_row


def test_gan_model_flops_by_hand():
    # generator fields mu, sigma 2-2-1: 12 each; discriminator f 2-2-1: 12,
    # g 2-2-2: 16; 3 joint points, 3 real points of f and g;
    # heads zeta 1-2-1: 8, ell 2, xi 2-2-1 twice: 24, m twice: 4
    per_row = 3 * (24 + 28) + 3 * 28 + 38
    assert sde_gan.forward_flops_per_row(GAN, {"seq_len": 3}) == per_row
    assert sde_gan.model_flops_per_step(
        GAN, {"seq_len": 3, "batch": 2}) == 3 * 2 * per_row


def test_pallas_bytes_of_the_fused_latent_step_by_hand(monkeypatch):
    from repro.core.sde import LatentSDEConfig, latent_sde_init
    from repro.launch.steps import make_latent_sde_optimizer, make_latent_sde_step

    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
    cfg = LatentSDEConfig(data_dim=2, hidden_dim=3, context_dim=4,
                          initial_noise_dim=2, width=5, num_steps=2,
                          use_pallas_kernels=True)
    params = latent_sde_init(jax.random.PRNGKey(0), cfg)
    init, update = make_latent_sde_optimizer(1e-2)
    step = jax.jit(make_latent_sde_step(cfg, update, 8, 3))
    got = pallas.bytes_per_step(step, params, init(params),
                                jax.random.PRNGKey(1))
    a = 8 * 4 * 4  # one (8 rows, 3 latent + 1 KL channel) float32 array
    key, n, dt = 8, 4, 4  # the path key, the step index, a float scalar
    forward = (key + n + 2 * dt + 4 * a + 2 * a) + (6 * a + dt + a)
    backward = ((key + n + dt + a)          # the step's increment, redrawn
                + (5 * a + dt + a)          # reverse phase 2
                + (6 * a + dt + a)          # reverse phase 1
                + (5 * a + dt + a)          # local VJP, phase 2's part
                + (4 * a + dt + 2 * a)      # local VJP, backward phase 1
                + (3 * a + dt + 4 * a))     # local VJP, backward phase 2
    assert got == 2 * (forward + backward)
