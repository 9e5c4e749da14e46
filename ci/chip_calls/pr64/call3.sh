# PR 64 call 3 (one chip): the slow read off the main thread, with glibc's arenas as they are and held to one (reads3.py).
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr64/call3; mkdir -p $OUT
for how in as-it-is one-arena; do
  echo "== $how"; python3 ci/chip_calls/pr64/reads3.py $how > $OUT/$how.log 2>&1; echo "rc=$?"
  grep -a "^{\|^mallopt\|Traceback\|Error" $OUT/$how.log | cut -c 1-600
done
