#!/usr/bin/env bash
# Launch hygiene for accelerator runs (olmax/HomebrewNLP idiom):
#   scripts/launch.sh <entrypoint.py|-m module> [args...]
#
# - tcmalloc, when present, replaces glibc malloc (host-side arena
#   assembly and numpy batch planning allocate heavily);
# - TF_CPP_MIN_LOG_LEVEL=4 silences the TF/XLA dataset warning spam;
# - REPRO_HOST_DEVICES=N forces N host platform devices (the forced-mesh
#   CI/bench topology; unset = real device count) — refused on a TPU
#   host, where the program must run on the chips;
# - REPRO_PALLAS_INTERPRET=0/1 overrides the Pallas interpret-mode policy
#   (see src/repro/kernels/common.py) — exported through untouched.
set -euo pipefail

TCMALLOC=/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4
if [[ -z "${LD_PRELOAD:-}" && -e "$TCMALLOC" ]]; then
  export LD_PRELOAD="$TCMALLOC"                 # faster malloc
  export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000
fi
export TF_CPP_MIN_LOG_LEVEL=4                   # no dataset warnings

if [[ -n "${REPRO_HOST_DEVICES:-}" ]]; then
  if [[ -e /dev/accel0 || -c /dev/vfio/0 ]]; then
    echo "launch.sh: REPRO_HOST_DEVICES forces CPU devices; unset it on a TPU host" >&2
    exit 2
  fi
  export XLA_FLAGS="--xla_force_host_platform_device_count=${REPRO_HOST_DEVICES} ${XLA_FLAGS:-}"
fi

REPO_ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
export PYTHONPATH="${REPO_ROOT}/src${PYTHONPATH:+:${PYTHONPATH}}"

exec python "$@"
