#!/usr/bin/env bash
# CI pipeline (ref role: the reference's Jenkinsfile stages —
# lint -> build -> unit tests -> integration).  Stages:
#   lint     stdlib AST linter over the whole tree
#   native   build the C runtime pieces (recordio)
#   test     full pytest suite on an 8-device virtual CPU mesh
#   entry    driver entry points: compile-check entry(), dryrun 8-dev
# Usage: ci/run.sh [lint|native|test|entry|all]
set -euo pipefail
cd "$(dirname "$0")/.."

stage="${1:-all}"

run_lint() {
  python ci/lint.py
}

run_native() {
  # the recordio module self-builds its .so from src/recordio on
  # first use; force a clean rebuild and require the native backend
  rm -f incubator_mxnet_tpu/lib/librecordio.so
  python - <<'EOF'
import incubator_mxnet_tpu.recordio as r
name = r.backend_name()
print("recordio backend:", name)
assert name == "native", "native recordio failed to build"
EOF
  # the C predict ABI (deployment to C clients)
  make -C src/c_predict
  # the C training ABI (cpp-package analog)
  make -C src/c_train
  # the general C API (NDArray / imperative invoke / KVStore)
  make -C src/c_api
  # the native JPEG batch decoder: force a clean SELF-build into the
  # package lib dir — the path the runtime actually loads from
  rm -f incubator_mxnet_tpu/lib/libmxtpu_imgdec*.so
  python - <<'EOF'
from incubator_mxnet_tpu.image import native_dec
assert native_dec.available(), "native image decoder failed to build"
print("imgdec backend: native")
EOF
}

run_test() {
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  JAX_PLATFORMS=cpu python -m pytest tests/ -q
}

run_entry() {
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  JAX_PLATFORMS=cpu python - <<'EOF'
import __graft_entry__ as g
g.dryrun_multichip(8)
print("dryrun ok")
EOF
}

case "$stage" in
  lint)   run_lint ;;
  native) run_native ;;
  test)   run_test ;;
  entry)  run_entry ;;
  all)    run_lint; run_native; run_test; run_entry ;;
  *) echo "unknown stage: $stage" >&2; exit 2 ;;
esac
