#!/usr/bin/env python
"""Packaging for sparkdl-tpu.

Mirrors the reference's packaging posture (reference ``setup.py``): the
tests package is excluded from wheels unless ``--with-tests`` is passed,
and runtime requirements are kept minimal — jax is the compute substrate
and cloudpickle ships user mains (reference contract
``runner_base.py:82-83``); tf/torch/pyspark are optional integrations
imported only if the user already uses them.
"""

import sys

from setuptools import find_packages, setup

exec(open("sparkdl_tpu/version.py").read())  # defines __version__

if "--with-tests" in sys.argv:
    sys.argv.remove("--with-tests")
    packages = find_packages(exclude=[])
else:
    packages = find_packages(exclude=["tests", "tests.*"])

setup(
    name="sparkdl-tpu",
    version=__version__,  # noqa: F821
    packages=packages,
    python_requires=">=3.10",
    install_requires=[
        "numpy",
        "cloudpickle",
        "jax>=0.9.0",
        "flax",
        "optax",
        "einops",
    ],
    extras_require={
        "tf": ["tensorflow"],
        "torch": ["torch"],
        "spark": ["pyspark>=3.2"],
        "checkpoint": ["orbax-checkpoint"],
    },
    description=(
        "TPU-native distributed deep learning: HorovodRunner, Horovod "
        "collective shim on XLA/ICI, and JAX gradient-boosted-tree "
        "estimators with the spark-deep-learning API surface."
    ),
    author="sparkdl-tpu developers",
    license="Apache 2.0",
)
