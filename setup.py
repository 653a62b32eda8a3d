"""Packaging (reference parity: setup.py + cmake/pip_install).

The package is pure Python over jax, written for jax 0.9 (no shims for
older releases). The native core (src/ffcore/libffcore.so) is built from
the committed sources on first use by flexflow_tpu.native.ensure_built();
without a toolchain set use_native_search=False (the Python search is the
reference semantics)."""
from setuptools import find_packages, setup

setup(
    name="flexflow-tpu",
    version="0.1.0",
    description=(
        "TPU-native automatic-parallelization DNN framework with the "
        "capabilities of FlexFlow/Unity (JAX/XLA/Pallas/pjit)"
    ),
    packages=find_packages(include=["flexflow_tpu", "flexflow_tpu.*"]),
    python_requires=">=3.10",
    install_requires=["jax>=0.9,<0.10", "numpy"],
    extras_require={
        "frontends": ["torch", "onnx"],
        "checkpoint": ["orbax-checkpoint"],
    },
    include_package_data=True,
)
