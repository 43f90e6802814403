# The single runtime image: controller-manager, SCI servers, and the
# contract containers (load/train/serve entrypoints) all live in this
# package — commands select the role (see config/ and controller/crs.py).
# The tpu extra pins the libtpu that matches the pinned jax (pyproject.toml).
FROM python:3.12-slim AS build
RUN apt-get update && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*
WORKDIR /src
COPY native/nbwatch.cc native/
RUN g++ -O2 -o /usr/local/bin/nbwatch native/nbwatch.cc

FROM python:3.12-slim
COPY --from=build /usr/local/bin/nbwatch /usr/local/bin/nbwatch
WORKDIR /app
COPY pyproject.toml ./
COPY substratus_tpu ./substratus_tpu
RUN pip install --no-cache-dir ".[grpc,tpu]"
WORKDIR /content
ENTRYPOINT ["python", "-m", "substratus_tpu.serve.main"]
