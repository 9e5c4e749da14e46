# PR 36, call 5: where the driver thread is when it is under NEITHER of its two spans
# (`lib/token_path.py` prints the holes by side since this call), both cells traced once.
OUT=/root/repo/chiprun_out/pr36; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_compile_cache_call}
run() { # tree cell seed trace tag [extra args]
  local tree=$1 W=$2 seed=$3 tr=$4 tag=$5; shift 5
  (cd _check/$tree && timeout 1500 python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace $tr "$@" > $OUT/$tag.log 2>&1; echo rc=$? $tag)
  grep -a "^\[token_path\]\|^\[program_spans\]" $OUT/$tag.log | cut -c 1-1800
  grep -a "^{" $OUT/$tag.log | tail -1 | cut -c 1-${CUT:-6000}
  [ "$tr" = 1 ] && cp _check/$tree/.perfbench_out/$W/last_run.json $OUT/last_run_$tag.json
}
C=internlm2-serve-chat; B=jamba2-serve-chat-burst
run final $C 1000000007 0 c5_warm_c
run final $C 1357911131 1 c5_chat_traced
run final $B 1000000007 0 c5_warm_b
run final $B 1357911131 1 c5_burst_traced
