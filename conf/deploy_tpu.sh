#!/usr/bin/env bash
# Deploy the framework to every worker of a TPU pod slice.
#
# TPU-native replacement for the reference's EC2 deploy
# (/root/reference/conf/deploy.sh:5-13 cross-compiles a Go binary and scp's
# it per host).  Python needs no cross-compile: we rsync the package + conf
# to all workers of the slice with one gcloud fan-out command.
#
# Usage: conf/deploy_tpu.sh <tpu-name> <zone> [project]
set -euo pipefail

TPU=${1:?tpu-vm name}
ZONE=${2:?zone}
PROJECT=${3:-$(gcloud config get-value project)}
REPO_DIR=$(cd "$(dirname "$0")/.." && pwd)

tar -C "$REPO_DIR" -czf /tmp/dissem_tpu.tgz \
    distributed_llm_dissemination_tpu conf benchmark BENCHMARK.json

gcloud compute tpus tpu-vm scp /tmp/dissem_tpu.tgz "$TPU":/tmp/ \
    --zone "$ZONE" --project "$PROJECT" --worker=all

gcloud compute tpus tpu-vm ssh "$TPU" --zone "$ZONE" --project "$PROJECT" \
    --worker=all --command \
    'mkdir -p ~/dissem && tar -C ~/dissem -xzf /tmp/dissem_tpu.tgz'
echo "deployed to all workers of $TPU"
