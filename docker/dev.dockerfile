# Development image for lingvo_tpu (ref lingvo/docker/dev.dockerfile).
#
# Build:  docker build -f docker/dev.dockerfile -t lingvo-tpu-dev .
# Run:    docker run --rm -it lingvo-tpu-dev bash
# On Cloud TPU VMs, use the libtpu-enabled jax install instead (see below).

FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
    build-essential make g++ git && rm -rf /var/lib/apt/lists/*

WORKDIR /workspace/lingvo_tpu
COPY pyproject.toml README.md ./
COPY lingvo_tpu ./lingvo_tpu
COPY tools ./tools
COPY tests ./tests
COPY __graft_entry__.py ./

# CPU jax by default; on TPU VMs replace with:
#   pip install 'jax[tpu]' -f https://storage.googleapis.com/jax-releases/libtpu_releases.html
RUN pip install --no-cache-dir -e .[tb,test] jax[cpu]

# build the native input-pipeline library once at image build
RUN make -C lingvo_tpu/ops/cc

CMD ["python", "-m", "pytest", "tests/", "-q"]
