# PR 36, call 1: internlm2-serve-chat, parent against change.
# _check/parent = git archive of 3f36658 (its own benchmark files: untraced pairs)
# _check/parent_new = the parent's program under this PR's benchmark files (what the
#   driver runs traced: the nine new readers must give None there and the run must end)
# _check/change = git archive $(git write-tree)
# _check/keep = _check/change with ONE line of perfbench/lib/xplane.py:reduce_dir patched
#   (on this throw-away machine only) so that the trace directory is copied before it is removed
OUT=/root/repo/chiprun_out/pr36; mkdir -p $OUT
# one compile cache for the four trees of a call: their jitted programs are the same text
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call
run() { # tree cell seed trace tag [extra args]
  local tree=$1 W=$2 seed=$3 tr=$4 tag=$5; shift 5
  (cd _check/$tree && timeout 900 python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace $tr "$@" > $OUT/$tag.log 2>&1; echo rc=$? $tag)
  grep -a "^\[token_path\]\|^\[program_spans\]" $OUT/$tag.log | cut -c 1-400
  grep -a "^{" $OUT/$tag.log | tail -1 | cut -c 1-${CUT:-700}
  [ "$tr" = 1 ] && cp _check/$tree/.perfbench_out/$W/last_run.json $OUT/last_run_$tag.json
}
C=internlm2-serve-chat; B=jamba2-serve-chat-burst
run change $C 1000000007 0 warm_c            # the machine's first run: compiles
# the chat cell: four interleaved pairs, untraced
run parent $C 2147483999 0 chat_p1; run change $C 2147483999 0 chat_c1
run change $C 3050607011 0 chat_c2; run parent $C 3050607011 0 chat_p2
run parent $C 912345677 0 chat_p3;  run change $C 912345677 0 chat_c3
run change $C 2718281829 0 chat_c4; run parent $C 2718281829 0 chat_p4
# traced: change (all nine are numbers), parent under the new benchmark files (all nine absent)
CUT=6000 run change $C 4242424243 1 chat_c_traced
CUT=6000 run parent_new $C 4242424243 1 chat_pn_traced
