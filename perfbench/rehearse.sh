#!/bin/sh
# The rehearsals of the on-chip-measurement guide (section 2), before any
# chip time. From the root of the repo:
#
#   sh perfbench/rehearse.sh          # 1 + 2: every cell, tiny, on the CPU
#   sh perfbench/rehearse.sh full     # 3 as well: full-size compiles for a
#                                     # described v5e:2x2 (minutes, ~40 GB RAM)
#
# 1. every cell end to end at a tiny size from the tiny files under
#    perfbench/tests/tiny (result line: platform cpu, counts only);
# 2. the training cell on four virtual devices;
# 3. both configurations' programs compiled by the chip's compiler at full
#    size, memory_analysis() printed (PERF.md records it).
set -e
export JAX_PLATFORMS=cpu
export XLA_FLAGS=--xla_force_host_platform_device_count=4
TINY=perfbench/tests/tiny/BENCHMARK.json
for cell in chat-steady longprompt-closed mixed-queue zero3-dp4-seq8k; do
  for trace in 0 1; do
    echo "== $cell --trace $trace (tiny, cpu)"
    python3 perfbench/run.py --benchmark $TINY --workload $cell --seed 1 \
      --seconds 4 --trace $trace --rehearse 2>/dev/null | tail -n 1
  done
done
echo "== sweep (tiny, cpu)"
python3 perfbench/sweep.py --benchmark $TINY --workload chat-steady \
  --rates 4 8 --seconds 3 --rehearse --keep-going 2>/dev/null | tail -n 1
if [ "$1" = "full" ]; then
  python3 perfbench/compile_full.py serve mistral-7b-l16-serve 32 64 2>&1 | grep "^frame"
  python3 perfbench/compile_full.py train mistral-7b-l8-zero3 train-seq8k-mb1 2>&1 | grep "^ZeRO"
fi
