# Container image for the vtpu-service control plane (the reference ships
# vc-scheduler / vc-controller-manager / vc-webhook-manager images via its
# installer; the rebuild packs the combined daemon + CLI into one image).
FROM python:3.12-slim

WORKDIR /opt/volcano-tpu
COPY pyproject.toml README.md ./
COPY volcano_tpu ./volcano_tpu
RUN pip install --no-cache-dir . && mkdir -p /var/lib/vtpu
# The XLA compile cache is placed from outside (scheduler.py sets no
# directory where this is set); keep it on the state volume.
ENV JAX_COMPILATION_CACHE_DIR=/var/lib/vtpu/xla_cache

VOLUME /var/lib/vtpu
EXPOSE 11250
ENTRYPOINT ["vtpu-service"]
CMD ["--bind-address", "0.0.0.0", "--listen-port", "11250", \
     "--state-path", "/var/lib/vtpu/state.ckpt"]
