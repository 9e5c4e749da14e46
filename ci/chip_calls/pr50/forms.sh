#!/bin/bash
# `step_forms.py` for each of WHICH (kimi, pangu) on SIDES (parent, change, change,
# parent): the programs alone, a compile cache of its own a side.
mkdir -p chiprun_out/pr50
for which in $WHICH; do
  for side in ${SIDES:-parent change change parent}; do
    dir=$([ $side = parent ] && echo _check/parent || echo .)
    echo "=== $which $side"
    JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call/$side \
      python3 ci/chip_calls/pr50/step_forms.py $dir $which 2>> chiprun_out/pr50/step_forms_${which}_$side.err | cut -c1-600
  done
done
