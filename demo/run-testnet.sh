#!/usr/bin/env bash
# Launch the localhost testnet built by build-conf.sh: N nodes over the
# socket proxy split, each with a dummy app bot that commits blocks and
# trickles transactions (reference: demo/scripts/run-testnet.sh —
# heartbeat 10ms, timeout 200ms, cache-size 50000).
set -euo pipefail

N=${1:-4}
CONF=${CONF:-/tmp/babble-tpu-demo}
PY=${PY:-python3}
# BACKEND=tpu gives the device backend to node 0 ONLY: a chip belongs to
# one process, so one validator's host owns it and the others run the CPU
# engine (a mixed cluster commits byte-identical blocks). A second process
# asking for the chip would fail at start (babble_tpu/tpu/runtime.py).
BACKEND=${BACKEND:-cpu}
MESH=${MESH:-0}          # BACKEND=tpu MESH=K shards node 0 over K chips
QUEUE_DEPTH=${QUEUE_DEPTH:-4}
BATCH_DEADLINE=${BATCH_DEADLINE:-0}
RATE=${RATE:-5}
REPO="$(cd "$(dirname "$0")/.." && pwd)"

pids=()
cleanup() { kill "${pids[@]}" 2>/dev/null || true; }
trap cleanup EXIT INT TERM

if [ "$BACKEND" = tpu ]; then
  echo "BACKEND=tpu: node0 runs the device backend, node1..$((N - 1)) the CPU backend (one process per chip)"
fi

for i in $(seq 0 $((N - 1))); do
  NODE_BACKEND=cpu
  NODE_MESH=0
  if [ "$i" -eq 0 ]; then
    NODE_BACKEND=$BACKEND
    NODE_MESH=$MESH
  fi
  PORT=$((1337 + i * 10))
  PROXY=$((1338 + i * 10))
  CLIENT=$((1339 + i * 10))
  SERVICE=$((8000 + i))
  # app bot first: the node dials the client at startup
  $PY "$REPO/demo/dummy_bot.py" --name "node$i" \
    --client-listen "127.0.0.1:$CLIENT" --proxy-connect "127.0.0.1:$PROXY" \
    --rate "$RATE" >"$CONF/node$i/bot.log" 2>&1 &
  pids+=($!)
  (cd "$REPO" && exec $PY -m babble_tpu run \
    --datadir "$CONF/node$i" \
    --listen "127.0.0.1:$PORT" \
    --proxy-listen "127.0.0.1:$PROXY" \
    --client-connect "127.0.0.1:$CLIENT" \
    --service-listen "127.0.0.1:$SERVICE" \
    --heartbeat 0.01 --timeout 0.2 --cache-size 50000 --sync-limit 500 \
    --consensus-backend "$NODE_BACKEND" \
    --mesh-devices "$NODE_MESH" \
    --dispatch-queue-depth "$QUEUE_DEPTH" \
    --dispatch-batch-deadline "$BATCH_DEADLINE" \
    --log warn) >"$CONF/node$i/log" 2>&1 &
  pids+=($!)
done

echo "testnet up: nodes on 1337/1347/..., /stats on http://127.0.0.1:800{0..$((N - 1))}"
echo "Ctrl-C to stop; logs under $CONF/node*/log"
wait
