# PR 38, call 4 (the log's `_call4.sh`; written as "call 3") (one chip): the two one-chip cells whose configurations run the changed block
# (mistral7b-train-1chip: fused blocks, `_rows_mesh` says no; internlm2-serve-chat: `mesh` is None),
# parent against change, interleaved, a seed a pair.
OUT=/root/repo/chiprun_out/pr38; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call
run() { # tree cell seed trace tag
  local tree=$1 W=$2 seed=$3 tr=$4 tag=$5
  (cd _check/$tree && timeout 900 python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace $tr > $OUT/$tag.log 2>&1; echo rc=$? $tag)
  grep -a "^{" $OUT/$tag.log | tail -1 | cut -c 1-${CUT:-900}
}
T=mistral7b-train-1chip; C=internlm2-serve-chat
run parent $T 912345677 0 t1_p1; run change $T 912345677 0 t1_c1
run change $C 2718281829 0 chat_c1; run parent $C 2718281829 0 chat_p1
run parent $C 1123581321 0 chat_p2; run change $C 1123581321 0 chat_c2
