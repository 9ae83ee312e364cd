"""Run a command that dies with the process that started it:

    python3 benchmark/with_parent.py <parent pid> <command> [arguments]

asks the kernel to send this process SIGKILL when its parent dies
(`prctl(PR_SET_PDEATHSIG)`, which outlives `exec`), checks that the parent is
still the one it was given, and becomes the command. So a harness that is
killed, by whatever signal, leaves no validator behind and no bound port.
Linux only, like the machines the benchmark runs on.
"""

import ctypes
import os
import signal
import sys

PR_SET_PDEATHSIG = 1


def main(argv) -> int:
    parent, command = int(argv[1]), argv[2:]
    libc = ctypes.CDLL(None, use_errno=True)
    if libc.prctl(PR_SET_PDEATHSIG, int(signal.SIGKILL), 0, 0, 0) != 0:
        print(f"[with_parent] prctl: errno {ctypes.get_errno()}", file=sys.stderr)
        return 1
    if os.getppid() != parent:
        return 1  # the parent died before the request was in place
    os.execvp(command[0], command)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
