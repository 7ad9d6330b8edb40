"""What the train and serve drivers share: model size and the compile cache."""
from __future__ import annotations

import argparse
import os

import jax

from repro.configs import get_config, get_smoke_config, list_archs

# fixed, inside the checkout: the path is part of the cache key, so a
# directory that moved between runs would never hit
_CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          os.pardir, os.pardir, os.pardir, ".jax_cache")


def add_model_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--arch", default="llava-1.5-7b", choices=list_archs())
    ap.add_argument("--full-width", action="store_true",
                    help="build the published config (all layers, published "
                         "widths, bf16) instead of the CPU smoke cut")


def model_config(args):
    """The ``--arch`` config at published width, or its smoke cut."""
    return get_config(args.arch) if args.full_width else get_smoke_config(args.arch)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and left
    alone; otherwise the cache lives in ``.jax_cache/`` at the checkout root.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.normpath(_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
