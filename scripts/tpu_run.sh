#!/usr/bin/env bash
# Soft-cancel run wrapper: deadline by cooperative cancellation, never a
# mid-dispatch SIGKILL.
#
# A `timeout`-style SIGKILL landing between a device dispatch and its
# readback kills the run with no checkpoint and no telemetry flush. This
# wrapper replaces `timeout N cmd` for any command built on
# spark_examples_tpu:
#
#   scripts/tpu_run.sh -d 600 [-g 60] -- python -m spark_examples_tpu.cli.main pca ...
#
# It exports SPARK_EXAMPLES_TPU_SOFT_DEADLINE=<now + deadline> (an
# ABSOLUTE timestamp, so child processes inherit the same wall-clock
# budget) and the driver checks it at block boundaries — the one place
# no dispatch is in flight — exiting cleanly with code 75
# (utils/softcancel.py). Only if the process is STILL alive a grace
# period past the deadline does the wrapper escalate: SIGTERM, then
# after another grace, SIGKILL (the last resort the soft path exists to
# make unnecessary). Before escalating it snapshots /proc state so a
# wedge is attributable.
#
# Exit status: the child's (75 = soft-cancelled, resume with the same
# --checkpoint-dir); 124 when the wrapper had to SIGTERM, 137 after a
# SIGKILL — if you ever see those, the deadline fired outside a
# cancellable section (file it).
set -u

DEADLINE_S=""
GRACE_S=60
while [ $# -gt 0 ]; do
  case "$1" in
    -d|--deadline) DEADLINE_S="$2"; shift 2 ;;
    -g|--grace) GRACE_S="$2"; shift 2 ;;
    --) shift; break ;;
    *) echo "tpu_run.sh: unknown option $1 (use -d SECONDS [-g SECONDS] -- cmd ...)" >&2; exit 2 ;;
  esac
done
if [ -z "${DEADLINE_S}" ] || [ $# -eq 0 ]; then
  echo "usage: tpu_run.sh -d DEADLINE_SECONDS [-g GRACE_SECONDS] -- cmd args..." >&2
  exit 2
fi

NOW=$(date +%s)
export SPARK_EXAMPLES_TPU_SOFT_DEADLINE=$((NOW + DEADLINE_S))

"$@" &
CHILD=$!

snapshot() {
  echo "tpu_run.sh: pre-escalation liveness snapshot of pid ${CHILD}:" >&2
  ps -o pid,stat,etime,wchan:24,args -p "${CHILD}" >&2 2>/dev/null || true
  cat "/proc/${CHILD}/status" 2>/dev/null | sed -n '1,6p' >&2 || true
}

ESCALATED=0
while kill -0 "${CHILD}" 2>/dev/null; do
  NOW=$(date +%s)
  OVER=$((NOW - SPARK_EXAMPLES_TPU_SOFT_DEADLINE))
  if [ "${OVER}" -ge $((2 * GRACE_S)) ] && [ "${ESCALATED}" -ge 1 ]; then
    echo "tpu_run.sh: ${OVER}s past deadline after SIGTERM; SIGKILL (last resort)." >&2
    kill -KILL "${CHILD}" 2>/dev/null
    ESCALATED=2
    break
  elif [ "${OVER}" -ge "${GRACE_S}" ] && [ "${ESCALATED}" -eq 0 ]; then
    echo "tpu_run.sh: ${OVER}s past deadline and still running; SIGTERM." >&2
    snapshot
    kill -TERM "${CHILD}" 2>/dev/null
    ESCALATED=1
  fi
  sleep 1
done

wait "${CHILD}"
RC=$?
# Rewrite only NON-clean exits: a child that finished its work (rc 0)
# or soft-cancelled (75) moments after the SIGTERM landed is a success
# being reported late, not a wedge — escalation is logged above either
# way, so the near-miss is still visible.
if [ "${ESCALATED}" -eq 1 ] && [ "${RC}" -ne 75 ] && [ "${RC}" -ne 0 ]; then RC=124; fi
if [ "${ESCALATED}" -eq 2 ] && [ "${RC}" -ne 75 ] && [ "${RC}" -ne 0 ]; then RC=137; fi
exit "${RC}"
