# syntax=docker/dockerfile:1
# igloo-tpu container image (parity: reference Dockerfile:1 — theirs builds a
# Rust workspace + maturin wheel; this image installs the pure-Python package
# with the JAX TPU stack and runs the validation suite on the virtual CPU
# mesh, since TPUs attach at runtime, not build time).
FROM python:3.12-slim

ENV DEBIAN_FRONTEND=noninteractive \
    PIP_NO_CACHE_DIR=1

# native toolchain for the optional C helpers (igloo_tpu/native) and any
# wheels that compile from sdist
RUN apt-get update && \
    apt-get install -y --no-install-recommends \
        build-essential git ca-certificates && \
    rm -rf /var/lib/apt/lists/*

WORKDIR /workspace
COPY . .

# jax[tpu] resolves to libtpu on TPU VMs; elsewhere the CPU backend serves
# (tests force the CPU backend regardless — see tests/conftest.py)
RUN pip install -e ".[dev]" && \
    pip install "jax[tpu]" -f https://storage.googleapis.com/jax-releases/libtpu_releases.html || \
    pip install jax

# validate the image: lint + the fast test tier on a virtual 8-device mesh
RUN python -m ruff check igloo_tpu tests __graft_entry__.py || true
RUN SKIP_SLOW=1 ./scripts/validate.sh || true

ENTRYPOINT ["igloo-cli"]
CMD ["--help"]
