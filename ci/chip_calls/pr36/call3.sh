# PR 36, call 3: traced against untraced on the change (a traced run whose --override
# changes nothing reads the end-to-end metrics too), a second clean traced chat run, and one
# traced run of the two cells this PR's builder had not yet read (their programs' text is the
# parent's: no parent side).
# _check/change = git archive $(git write-tree) + the working tree's perfbench/lib/token_path.py
OUT=/root/repo/chiprun_out/pr36; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_compile_cache_call}
run() { # tree cell seed trace tag [extra args]
  local tree=$1 W=$2 seed=$3 tr=$4 tag=$5; shift 5
  (cd _check/$tree && timeout 1500 python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace $tr "$@" > $OUT/$tag.log 2>&1; echo rc=$? $tag)
  grep -a "^\[token_path\]\|^\[program_spans\]" $OUT/$tag.log | cut -c 1-1600
  grep -a "^{" $OUT/$tag.log | tail -1 | cut -c 1-${CUT:-6000}
  [ "$tr" = 1 ] && cp _check/$tree/.perfbench_out/$W/last_run.json $OUT/last_run_$tag.json
}
C=internlm2-serve-chat; B=jamba2-serve-chat-burst
run change $C 1000000007 0 c3_warm_c
run change $C 5550001117 1 c3_chat_traced
run change $C 5550001117 0 c3_chat_untraced
run change $C 6660002227 1 c3_chat_traced_e2e --override rate_per_s=5.4
run change $B 6660002227 1 c3_burst_traced_e2e --override rate_per_s=22
run change $B 6660002227 0 c3_burst_untraced
run change kimi-linear-serve-longgen 7770003337 1 c3_longgen_traced
run change openpangu-serve-longctx 8880004447 1 c3_longctx_traced
