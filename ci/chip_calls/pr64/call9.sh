# PR 64 calls 9a / 9b (one chip, seconds each): does the chip tool carry a list named as jax names its entries? 9a leaves
# `programs-probe-cache` and its `-atime` stamp in the machine's cache directory; 9b looks for them on the next machine.
d=${JAX_COMPILATION_CACHE_DIR:-.jax_compile_cache}
ls -l $d/programs-* 2>&1 | head
if [ "$1" = leave ]; then
  echo '{"probe": "pr64"}' > $d/programs-probe-cache
  python3 -c "import sys,time; open(sys.argv[1],'wb').write(time.time_ns().to_bytes(8,'little'))" $d/programs-probe-atime
  ls -l $d/programs-*
fi
