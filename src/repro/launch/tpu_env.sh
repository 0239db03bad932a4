# TPU host environment for rollout/training runs.  Opt-in: source this
# before launching (`. src/repro/launch/tpu_env.sh`); nothing in the repo
# sets these for you, and every knob is guarded so sourcing on a dev box
# without the libraries is harmless.
#
#   tcmalloc        page-pool allocators (engine block pools, host-side
#                   swap store) churn large allocations; glibc malloc
#                   fragments under that load.
#   alloc report    silence tcmalloc's large-alloc warnings for the
#                   multi-GB parameter/optimizer buffers.
#   step marker     --xla_step_marker_location=1 marks the outer while
#                   loop (the decode loop) so profiler traces align AR
#                   steps instead of whole program entry.

_TCMALLOC=/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4
if [ -f "${_TCMALLOC}" ]; then
    export LD_PRELOAD="${_TCMALLOC}${LD_PRELOAD:+:${LD_PRELOAD}}"
    export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000
fi
unset _TCMALLOC

export XLA_FLAGS="--xla_step_marker_location=1${XLA_FLAGS:+ ${XLA_FLAGS}}"
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"

