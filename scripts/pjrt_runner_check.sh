#!/usr/bin/env bash
# End-to-end native-serving check on a real chip: export a GEMM as a raw
# PJRT executable from Python, then execute it with the C++ runner
# (csrc/pjrt_runner — no Python in the load/execute path) and compare the
# output byte-sum against the jitted Python run of the same inputs.
#
# The runner loads libtpu.so directly (no options needed). One process
# per chip: the Python export below exits, and so frees the chip, before
# the runner starts.
set -euo pipefail
cd "$(dirname "$0")/.."

# the one persistent compile cache (config.compile_cache_dir): placed
# from outside where JAX_COMPILATION_CACHE_DIR is set, else the checkout's
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$(cd "$(dirname "$0")/.." && pwd)/.jax_cache}"
mkdir -p "$JAX_COMPILATION_CACHE_DIR"

make -C csrc pjrt_runner

WORK=$(mktemp -d "${TMPDIR:-/tmp}/tdt_pjrt_check.XXXXXX")
trap 'rm -rf "$WORK"' EXIT
export EXE=$WORK/check.bin
read -r CMD_SUM < <(python - <<'EOF'
import os
import numpy as np, jax, jax.numpy as jnp, ml_dtypes
from triton_dist_tpu import aot

def pattern(nbytes):
    i = np.arange(nbytes, dtype=np.uint64)
    return ((i * 131) % 241 % 63).astype(np.uint8)

fn = lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32)
a = pattern(256*256*2).view(ml_dtypes.bfloat16).reshape(256, 256)
b = pattern(256*512*2).view(ml_dtypes.bfloat16).reshape(256, 512)
aot.export_pjrt(fn, (jnp.asarray(a), jnp.asarray(b)), os.environ["EXE"])
out = np.asarray(jax.jit(fn)(jnp.asarray(a), jnp.asarray(b)))
print(int(out.view(np.uint8).astype(np.uint64).sum()))
EOF
)

PLUGIN=$(python -c "import libtpu, os; print(os.path.join(os.path.dirname(libtpu.__file__), 'libtpu.so'))")
OPTS=()

OUT=$(./csrc/pjrt_runner "$PLUGIN" "$EXE" "${OPTS[@]}" \
      --input bf16:256x256 --input bf16:256x512 --iters 3 2>/dev/null | grep bytesum)
NATIVE_SUM=$(sed 's/.*bytesum=//' <<<"$OUT")
echo "python bytesum=$CMD_SUM native bytesum=$NATIVE_SUM"
[ "$CMD_SUM" = "$NATIVE_SUM" ] && echo "PJRT RUNNER CHECK OK" || { echo "MISMATCH"; exit 1; }
