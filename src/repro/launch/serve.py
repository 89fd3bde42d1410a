"""Serving launcher: the batching engine on a mesh of the devices present.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
        --smoke --requests 4

The helpers here — :func:`enable_compile_cache`, :func:`serving` and
:func:`init_params` — are the served path's set-up; ``chip_smoke.py``
drives the same code.
"""
import argparse
import contextlib
import dataclasses
import os
import pathlib
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_run_config, smoke_config
from repro.configs.base import ModelConfig, RunConfig
from repro.distributed import sharding as shd
from repro.launch import flags
from repro.launch.mesh import make_host_mesh
from repro.models import nn, transformer as tfm
from repro.serving.engine import Engine, Request

# <repo>/src/repro/launch/serve.py → <repo>
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set.  Otherwise the cache
    lives at ``<repo>/.jax_cache`` — one fixed path, because the path is
    part of the cache key and a directory that moves never hits.
    """
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(REPO_ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def serving(mesh):
    """The mesh and decode axis rules the engine runs under."""
    with mesh, nn.axis_rules(shd.make_rules("decode"), mesh=mesh):
        yield


def init_params(cfg: ModelConfig, mesh, *, seed: int = 0,
                dtype=jnp.bfloat16):
    """Seeded random parameters in ``dtype``, placed on ``mesh``.

    Created under ``jit`` with the decode rules' shardings as output
    shardings, so each device holds only its shard and no whole float32
    copy of the model is ever materialised on a device.
    """
    specs = {}

    def build(key):
        params, specs["tree"] = tfm.init_model(key, cfg)
        return jax.tree_util.tree_map(
            lambda x: x.astype(dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

    key = jax.random.PRNGKey(seed)
    abstract = jax.eval_shape(build, key)
    pspecs = shd.tree_pspecs_shaped(specs["tree"], abstract,
                                    shd.make_rules("decode"), mesh)
    return jax.jit(build, out_shardings=shd.tree_shardings(
        mesh, pspecs))(key)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=128)
    ap.add_argument("--latency-flags", action="store_true",
                    help="apply serving-grade latency flags (async "
                    "collectives + latency-hiding scheduler) before "
                    "backend init")
    args = ap.parse_args()

    if args.smoke:
        cfg = smoke_config(args.arch)
        rc = RunConfig(latency_flags=args.latency_flags)
    else:
        cfg = get_config(args.arch)
        rc = get_run_config(args.arch, "decode_32k")
        if args.latency_flags:
            rc = dataclasses.replace(rc, latency_flags=True)
    if rc.latency_flags:
        print(f"latency flags: {flags.apply_latency_flags()!r}")
    enable_compile_cache()
    mesh = make_host_mesh()

    with serving(mesh):
        params = init_params(cfg, mesh)
        engine = Engine(params, cfg, slots=args.slots,
                        capacity=args.capacity, rc=rc)
        t0 = time.time()
        for uid in range(args.requests):
            engine.submit(Request(uid=uid, prompt=[1 + uid, 2, 3],
                                  max_new_tokens=args.max_new))
        done = engine.run_to_completion()
        dt = time.time() - t0
    toks = sum(len(r.output) for r in done)
    for r in sorted(done, key=lambda r: r.uid):
        print(f"req {r.uid}: {r.output} ({r.status})")
    print(f"{toks} tokens in {dt:.1f}s on {len(jax.devices())} "
          f"{jax.devices()[0].device_kind} device(s)")


if __name__ == "__main__":
    main()
