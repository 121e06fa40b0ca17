# lddl_tpu image for TPU-VM hosts.
#
# TPU-native analogue of the reference's NGC images
# (docker/ngc_pyt.Dockerfile, ngc_paddle.Dockerfile): instead of an NGC
# CUDA base, start from a slim Python base and install the TPU-enabled
# jax wheels. On a TPU-VM the container must run with --privileged (or
# the TPU device flags) and host networking so libtpu can reach the
# chips; see docker/interactive.sh.
#
# Build:  docker build -f docker/tpu.Dockerfile -t lddl_tpu .

FROM python:3.12-slim-bookworm

ENV LANG=C.UTF-8 \
    LC_ALL=C.UTF-8 \
    PIP_NO_CACHE_DIR=1

RUN apt-get update -qq && \
    apt-get install -y --no-install-recommends \
        git vim tmux g++ make libjemalloc-dev wget && \
    rm -rf /var/lib/apt/lists/*

# The one installation the code is written against (setup.py pins the
# same versions; README "Running on the chip").
RUN pip install -U pip && \
    pip install jax==0.9.0 jaxlib==0.9.0 libtpu==0.0.34 \
        flax==0.12.3 optax==0.2.6 orbax-checkpoint==0.11.32 \
        numpy pyarrow transformers requests tqdm pytest

# The preprocessor is malloc-heavy on the host side; jemalloc is the same
# allocator swap the reference documents (README.md:22-28).
ENV LD_PRELOAD=/usr/lib/x86_64-linux-gnu/libjemalloc.so.2

WORKDIR /workspace/lddl_tpu
COPY . .
RUN pip install ./

# Pre-build the native WordPiece/pairing library into the *installed*
# copy (cd / so the import resolves to site-packages, not the source tree
# that docker/interactive.sh bind-mounts over). Runs using the mounted
# source tree still rebuild lazily on first use — g++ is in the image.
RUN cd / && python -c "from lddl_tpu.native.build import build_library; build_library(verbose=True)"
