#!/bin/sh
# Build the host C++ data plane shared library.
#   build.sh [OUTPUT.so]
# sparknet_tpu/data/jpeg_plane.py passes an OUTPUT named by a hash of this
# script, jpeg_plane.cpp and the host CPU (-march=native below), so a
# library is only ever loaded on the host and source revision it was built
# from. Built via a temp name + mv so a killed build never leaves a partial
# file under the real one.
set -e
cd "$(dirname "$0")"
out="${1:-libjpeg_plane.so}"
g++ -O3 -march=native -shared -fPIC -fopenmp -o "$out.tmp.$$" \
    jpeg_plane.cpp -ljpeg
mv "$out.tmp.$$" "$out"
echo "built $out"
