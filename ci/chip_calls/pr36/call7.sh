# PR 36, call 7: the final tree from `git archive $(git write-tree)` (_check/final), the parent
# (_check/parent = 3f36658) beside it: the chat cell traced and one untraced pair, chat-burst traced.
OUT=/root/repo/chiprun_out/pr36; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_compile_cache_call}
run() { # tree cell seed trace tag [extra args]
  local tree=$1 W=$2 seed=$3 tr=$4 tag=$5; shift 5
  (cd _check/$tree && timeout 1500 python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace $tr "$@" > $OUT/$tag.log 2>&1; echo rc=$? $tag)
  grep -a "^\[token_path\]\|^\[program_spans\]" $OUT/$tag.log | cut -c 1-1800
  grep -a "^{" $OUT/$tag.log | tail -1 | cut -c 1-6000
  if [ "$tr" = 1 ]; then cp _check/$tree/.perfbench_out/$W/last_run.json $OUT/last_run_$tag.json; fi
}
C=internlm2-serve-chat; B=jamba2-serve-chat-burst
run final $C 1000000007 0 c7_warm_c
run final $C 1928374655 1 c7_chat_traced
run parent $C 1928374655 0 c7_chat_p; run final $C 1928374655 0 c7_chat_f
run final $B 1928374655 1 c7_burst_traced
run final $B 1928374655 0 c7_burst_f
