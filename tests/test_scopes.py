"""The solve stack's named scopes (``repro.core.scopes``) in the compiled
SDE-GAN training step, at the size the benchmark's CPU tests cut its
``ou_gan`` cell to, with the exact adjoint as the cell runs it; and the
encoder's scope in the Latent-SDE step, which the GAN step lacks.

A scope is HLO metadata: the step must compile to the same program with
and without the scopes.  XLA names an instruction after the last component
of its ``op_name``, so the comparison strips the metadata and the
source-location tables and renames instructions and computations in order
of appearance."""

import contextlib
import json
import re

import jax
import pytest

from bench.models import latent_sde
from bench.models.sde_gan import program_config
from bench.tests import tiny
from repro.core import scopes
from repro.core.sde import discriminator_init, generator_init, latent_sde_init
from repro.launch.steps import (make_gan_optimizers, make_latent_sde_optimizer,
                                make_latent_sde_step, make_sde_gan_step)

SCOPES = (scopes.SOLVE, scopes.ADJOINT, scopes.BROWNIAN, scopes.FIELD)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r',?\s*(?<![\w])metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')


@pytest.fixture
def no_compile_cache():
    """A fresh compile for every call: the persistent cache (which another
    test in the process may have turned on) keys programs without their
    metadata, so it would hand the second compile the first one's
    executable, scopes and all."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _compiled_step() -> str:
    config = json.loads((tiny.REPO / "bench" / "configs" / "ou_gan.json")
                        .read_text())
    config["model"].update(tiny.CONFIGS["ou_gan"])
    cell = tiny.CELLS["ou_gan.train_b1024"]
    cfg = program_config(config, "highest")
    assert cfg.exact_adjoint
    (g_init, g_update), (d_init, d_update) = make_gan_optimizers(
        lr=1.0, constraint="clip")
    step = jax.jit(make_sde_gan_step(cfg, g_update, d_update, cell["batch"],
                                     cell["seq_len"], constraint="clip"))
    key = jax.random.PRNGKey(0)
    params = {"gen": generator_init(key, cfg),
              "disc": discriminator_init(jax.random.fold_in(key, 1), cfg)}
    return step.lower(params, g_init(params["gen"]), d_init(params["disc"]),
                      jax.random.PRNGKey(1)).compile().as_text()


def _compiled_latent_step() -> str:
    """The ``latent_sde_air`` cell's step (exact adjoint, fused kernels) at
    a few units and steps."""
    config = json.loads((tiny.REPO / "bench" / "configs" / "latent_sde_air.json")
                        .read_text())
    config["model"].update(hidden_dim=2, context_dim=3, initial_noise_dim=2,
                           width=4, num_steps=3)
    traffic = {"batch": 8, "seq_len": 4, "adjoint": "exact",
               "use_pallas_kernels": True}
    cfg = latent_sde.program_config(config, traffic, "highest")
    opt_init, opt_update = make_latent_sde_optimizer(config["optimiser"]["lr"])
    step = jax.jit(make_latent_sde_step(cfg, opt_update, traffic["batch"],
                                        traffic["seq_len"], adjoint="exact"))
    params = latent_sde_init(jax.random.PRNGKey(0), cfg)
    return step.lower(params, opt_init(params),
                      jax.random.PRNGKey(1)).compile().as_text()


def _program(hlo: str) -> str:
    """The compiled module with metadata, the source-location tables and
    instruction names taken out."""
    lines = hlo.splitlines()
    body = next(i for i, ln in enumerate(lines)
                if ln.startswith(("%", "ENTRY")))
    text = _METADATA.sub("", "\n".join(lines[:1] + lines[body:]))
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


def test_compiled_step_names_every_scope(no_compile_cache):
    names = _OP_NAME.findall(_compiled_step())
    for scope in SCOPES:
        assert any(scope in n.split("/") or f"({scope})" in n for n in names), (
            scope)
    adjoint = [n for n in names if scopes.ADJOINT in n]
    assert adjoint
    for n in adjoint:
        # the backward rules run only inside autodiff's transpose
        assert "transpose(" in n[:n.index(scopes.ADJOINT)], n


def test_scopes_change_no_program(monkeypatch, no_compile_cache):
    scoped = _compiled_step()
    monkeypatch.setattr(scopes, "scope", contextlib.nullcontext)
    jax.clear_caches()
    plain = _compiled_step()
    assert not any(s in n for n in _OP_NAME.findall(plain) for s in SCOPES)
    assert _program(scoped) == _program(plain)


def test_latent_step_names_its_encoder(no_compile_cache):
    """``sde.encode`` covers the GRU and the ``qz0``/``zeta`` heads, and
    their VJP under ``transpose(...)``; the GAN step has no encoder."""
    names = _OP_NAME.findall(_compiled_latent_step())
    encode = [n for n in names
              if scopes.ENCODE in n.split("/") or f"({scopes.ENCODE})" in n]
    assert encode
    assert any("transpose(" in n[:n.index(scopes.ENCODE)] for n in encode)
    assert any(scopes.SOLVE in n for n in names)
    assert not any(scopes.ENCODE in n for n in _OP_NAME.findall(_compiled_step()))
