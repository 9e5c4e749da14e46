# PR 64 call 1 (one chip): before any engine code, what the chip's client lets overlap (reads.py).
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr64/call1; mkdir -p $OUT
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset} MAX=${JAX_COMPILATION_CACHE_MAX_SIZE:-unset}"
env | grep -i "^JAX\|^XLA\|^TPU" | cut -c 1-200
d=${JAX_COMPILATION_CACHE_DIR:-$ROOT/.jax_compile_cache}; ls -ld $d $(dirname $d); ls $(dirname $d) | head; du -sh $d 2>/dev/null
grep -E " $(dirname $d)| $d| /root/.cache" /proc/mounts | cut -c 1-200
touch $(dirname $d)/.pr64_probe && echo "sibling writable" && rm -f $(dirname $d)/.pr64_probe
nproc
python3 ci/chip_calls/pr64/reads.py > $OUT/reads.log 2>&1; echo "rc=$?"
grep -a "^{\|^cache dir\|Traceback\|Error" $OUT/reads.log | cut -c 1-1500
