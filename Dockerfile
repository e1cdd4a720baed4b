# Runtime image for the control plane + oracle service.
# The TPU runtime (libtpu) comes from the host environment on TPU VMs;
# for CPU-only control-plane replicas the jax[cpu] wheel suffices.
FROM python:3.12-slim AS build
RUN apt-get update && apt-get install -y --no-install-recommends \
    g++ make && rm -rf /var/lib/apt/lists/*
WORKDIR /src
COPY . .
# clean first: a host-built .so copied in (despite .dockerignore) must
# never ship — rebuild against this image's toolchain.
RUN make -C native clean && make -C native \
    && pip wheel --no-deps -w /wheels .

FROM python:3.12-slim
RUN pip install --no-cache-dir "jax[cpu]" numpy
COPY --from=build /wheels /wheels
RUN pip install --no-cache-dir /wheels/*.whl
COPY --from=build /src/native/build/libkueue_native.so \
    /usr/local/lib/kueue_tpu/libkueue_native.so
ENV KUEUE_TPU_NATIVE_LIB=/usr/local/lib/kueue_tpu/libkueue_native.so
# The package is installed, not a checkout, so the compile cache's
# default (<checkout>/.jax_cache, kueue_tpu/utils/startup.py) would land
# in site-packages and die with the container. deploy/ mounts a volume
# here for both the oracle and the engine.
ENV JAX_COMPILATION_CACHE_DIR=/var/cache/kueue-tpu/jax
# The oracle serving boundary (snapshot-in / verdicts-out). Bind all
# interfaces so the published port actually reaches the service.
EXPOSE 7461
ENTRYPOINT ["kueue-tpu-oracle", "--host", "0.0.0.0", "--port", "7461"]
