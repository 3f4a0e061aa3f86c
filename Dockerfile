# kepler-tpu container image.
#
# Reference parity: `Dockerfile` upstream builds a static Go binary into a
# UBI9-micro image. Here the runtime is Python+JAX, so the image is a slim
# Python base with the package installed and the native C++ procfs scanner
# pre-built (so the runtime never needs a compiler).
#
# Build:  docker build -t kepler-tpu:latest .
# The same image serves both roles:
#   node agent :  kepler-tpu  (default CMD)
#   aggregator :  kepler-tpu-aggregator  (owns the chip: needs a TPU-visible
#                 runtime, e.g. a node pool with TPU drivers. With
#                 `tpu.platform: tpu` it refuses to start without one;
#                 `tpu.platform: cpu` runs it on the CPU on purpose)

FROM python:3.12-slim AS build

RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /src
COPY pyproject.toml README.md ./
COPY kepler_tpu ./kepler_tpu
RUN pip install --no-cache-dir --prefix=/install . \
    # pre-build the native scanner so the runtime image needs no compiler
    && python -c "import sys; sys.path.insert(0, '/install/lib/python3.12/site-packages'); \
from kepler_tpu.native import ensure_built; print(ensure_built())"

FROM python:3.12-slim

COPY --from=build /install /usr/local

# agent reads host /proc and /sys mounted read-only by the DaemonSet
# (manifests/k8s/daemonset.yaml); override via --host.procfs/--host.sysfs
EXPOSE 28282 28283
ENTRYPOINT ["kepler-tpu"]
CMD ["--host.sysfs=/host/sys", "--host.procfs=/host/proc"]
