# PR 36, call 8 (after the review): the final tree from `git archive $(git write-tree)` (_check/final),
# the parent (_check/parent = 3f36658) beside it, and the parent's program under this PR's benchmark
# files (_check/parent_new): both cells traced on the final tree (all twelve new metrics), the chat cell
# traced on parent_new (all twelve absent, the run ends with a result), one untraced chat pair and
# two interleaved untraced chat-burst pairs (parent, final, final, parent).
OUT=/root/repo/chiprun_out/pr36; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_compile_cache_call}
run() { # tree cell seed trace tag [extra args]
  local tree=$1 W=$2 seed=$3 tr=$4 tag=$5; shift 5
  (cd _check/$tree && timeout 1500 python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace $tr "$@" > $OUT/$tag.log 2>&1; echo rc=$? $tag)
  grep -a "^\[token_path\]\|^\[program_spans\]" $OUT/$tag.log | cut -c 1-1800
  grep -a "^{" $OUT/$tag.log | tail -1 | cut -c 1-6000
}
C=internlm2-serve-chat; B=jamba2-serve-chat-burst
run final $C 2718281829 1 c8_chat_traced
run parent_new $C 2718281829 1 c8_chat_pn_traced
run parent $C 1414213563 0 c8_chat_p; run final $C 1414213563 0 c8_chat_f
run final $B 2236067977 1 c8_burst_traced
run parent $B 1732050809 0 c8_burst_p1; run final $B 1732050809 0 c8_burst_f1
run final $B 2645751311 0 c8_burst_f2; run parent $B 2645751311 0 c8_burst_p2
