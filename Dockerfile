# Operator + tpu-engine image. One image serves both entrypoints
# (reference ships a single manager image; here the tpu driver's sidecar
# shares the package):
#   python -m coraza_kubernetes_operator_tpu.cmd.operator     (control plane)
#   python -m coraza_kubernetes_operator_tpu.cmd.tpu_engine   (data plane)
FROM python:3.12-slim AS base

WORKDIR /app

# CPU jax by default; TPU nodes swap in the libtpu wheel at deploy time.
RUN pip install --no-cache-dir "jax>=0.4.30" numpy pyyaml

COPY coraza_kubernetes_operator_tpu/ coraza_kubernetes_operator_tpu/
COPY native/ native/

# Build the native host runtime. A failed build fails the image: without
# the library the sidecar serves on the Python tensorizer and says so
# only in /waf/v1/stats (native.available).
RUN apt-get update && apt-get install -y --no-install-recommends g++ make \
    && rm -rf /var/lib/apt/lists/* && make -C native

RUN useradd -u 65532 -m nonroot
USER 65532

ENV PYTHONPATH=/app
ENTRYPOINT ["python", "-m", "coraza_kubernetes_operator_tpu.cmd.operator"]
