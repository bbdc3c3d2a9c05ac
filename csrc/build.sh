#!/bin/sh
# Build the native host-runtime extension (libbf_runtime.so).
# Invoked lazily by bluefog_tpu.runtime.native; safe to run by hand.
#
# SANITIZE=thread|address builds an instrumented variant alongside the
# normal artifact (build/libbf_runtime.tsan.so / .asan.so) — used by
# `make tsan` / `make asan`, which point the Python runtime at it via
# BLUEFOG_NATIVE_SO (see docs/static_analysis.md).
set -e
cd "$(dirname "$0")"
mkdir -p build
case "${SANITIZE:-}" in
  thread)
    exec g++ -O1 -g -shared -fPIC -std=c++17 -pthread \
        -fsanitize=thread -fno-omit-frame-pointer \
        -o build/libbf_runtime.tsan.so bf_runtime.cc
    ;;
  address)
    exec g++ -O1 -g -shared -fPIC -std=c++17 -pthread \
        -fsanitize=address -fno-omit-frame-pointer \
        -o build/libbf_runtime.asan.so bf_runtime.cc
    ;;
  "")
    # built beside the target and renamed: a process that loads the library
    # while another rebuilds it never maps a half-written file
    g++ -O2 -shared -fPIC -std=c++17 -pthread \
        -o "build/libbf_runtime.so.$$" bf_runtime.cc
    exec mv -f "build/libbf_runtime.so.$$" build/libbf_runtime.so
    ;;
  *)
    echo "build.sh: unknown SANITIZE='$SANITIZE' (thread|address)" >&2
    exit 2
    ;;
esac
