# Runtime image for a pilosa-tpu node. JAX/TPU wheels are environment
# specific; install the matching jax[tpu] for your runtime.
FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /pilosa-tpu
COPY pilosa_tpu ./pilosa_tpu
COPY chip_smoke.py Makefile ./

RUN pip install --no-cache-dir numpy jax \
    && make native

# One process owns the chip; compiled programs persist here.
ENV JAX_COMPILATION_CACHE_DIR=/data/.jax_cache
VOLUME /data
EXPOSE 10101
ENTRYPOINT ["python", "-m", "pilosa_tpu.cli"]
CMD ["server", "-d", "/data", "-b", "0.0.0.0:10101"]
