#!/bin/sh
# Sanitizer CI for the native core — the discipline the reference keeps in
# scripts/travis/travis_script.sh:53-60 (TSAN Debug run of the unit suite).
# Builds native_smoke + the threaded stress driver under ASan+UBSan and
# TSan and runs both; output is recorded to native/SANITIZE.log (committed,
# so every round's sanitizer status is auditable in-repo).
#
# Usage: sh native/run_sanitizers.sh
set -eu
cd "$(dirname "$0")"
# keep in sync with Makefile NATIVE_SRCS, CMakeLists.txt, and
# dmlc_tpu/native/__init__.py _SRCS — a .cc missing here is a silent
# sanitizer coverage gap
SRCS="src/parse.cc src/reader.cc src/recordio.cc"
LOG=SANITIZE.log
: > "$LOG"

run() {
  name="$1"; flags="$2"
  echo "== $name ==" | tee -a "$LOG"
  g++ -O1 -g -std=c++17 -pthread -fno-omit-frame-pointer $flags \
      -o "build/smoke_$name" test/native_smoke.cc $SRCS 2>>"$LOG"
  g++ -O1 -g -std=c++17 -pthread -fno-omit-frame-pointer $flags \
      -o "build/stress_$name" test/stress_reader.cc $SRCS 2>>"$LOG"
  for bin in "build/smoke_$name" "build/stress_$name"; do
    echo "-- $bin" | tee -a "$LOG"
    if "./$bin" >>"$LOG" 2>&1; then
      echo "   PASS" | tee -a "$LOG"
    else
      echo "   FAIL (rc=$?)" | tee -a "$LOG"
      exit 1
    fi
  done
}

mkdir -p build
run asan "-fsanitize=address,undefined"
run tsan "-fsanitize=thread"
echo "sanitizers: ALL CLEAN" | tee -a "$LOG"
