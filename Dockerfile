# zeebe-tpu broker/gateway image (reference deployment parity: the upstream
# project ships a Dockerfile for its dist; this is the tpu-native analogue).
#
# Build:  docker build -t zeebe-tpu .
# Run:    docker run -p 26500:26500 zeebe-tpu            # single dev broker
# Or bring up the 3-broker TCP cluster: docker compose -f docker/compose.yml up
#
# The image runs CPU JAX by default; on a TPU VM mount the libtpu runtime and
# drop the JAX_PLATFORMS pin: the broker then resolves the chip in-process at
# start-up and fails there if none answers (one broker process per chip).

FROM python:3.12-slim

# gcc: the native msgpack codec (zeebe_tpu/native/codec.c) builds on demand
# at first boot; everything degrades to pure Python without it
RUN apt-get update \
    && apt-get install -y --no-install-recommends gcc libc6-dev \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY docker/requirements.txt /app/docker/requirements.txt
RUN pip install --no-cache-dir -r docker/requirements.txt

COPY zeebe_tpu /app/zeebe_tpu

ENV PYTHONUNBUFFERED=1 \
    JAX_PLATFORMS=cpu \
    ZEEBE_DATA_DIR=/usr/local/zeebe/data

RUN mkdir -p /usr/local/zeebe/data
VOLUME /usr/local/zeebe/data

# 26500 gateway gRPC · 26600 cluster messaging · 9600 management HTTP
EXPOSE 26500 26600 9600

ENTRYPOINT ["python", "-m", "zeebe_tpu.standalone"]
CMD ["--port", "26500", "--management-port", "9600", \
     "--data-dir", "/usr/local/zeebe/data"]
