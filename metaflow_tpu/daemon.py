"""Persistent scheduler daemon: warm flow launches over a unix socket.

The local scheduler's only real launch cost is process cold start —
interpreter boot, framework+jax imports, worker-pool warmup. The fork
pool (runtime.py) already dodges that per *task*; this daemon dodges it
per *run*: a long-lived process pre-imports the heavy modules once, and
each launch is a fork that inherits the warm interpreter, with the
client's stdio file descriptors passed over the socket (SCM_RIGHTS) so
the run is fully transparent — output, exit code, Ctrl-C all behave as
if the flow ran in the client.

    python -m metaflow_tpu.daemon start            # serve (foreground)
    python -m metaflow_tpu.daemon start --detach   # serve (background)
    python -m metaflow_tpu.daemon run flow.py run --alpha 0.5
    python -m metaflow_tpu.daemon stop|status

The reference has no equivalent (its runtime pays the cold start every
run); this is a TPU-first addition in the spirit of its fast-launch work
(metaflow_profile timings). Not measured on the chip.

Caveat (dev tool, by design): the fork inherits the daemon's module
cache, so edits to *framework* code need a daemon restart; the flow file
itself is re-imported fresh in every child.
"""

import hashlib
import json
import os
import runpy
import signal
import socket
import struct
import sys
import tempfile
import threading
import traceback

from . import knobs

# Handshake: every request carries the protocol version and a token hashed
# over the whole package's source, so a stale client from an older
# checkout cannot silently drive a newer daemon — and a daemon whose
# warm-imported modules predate a git pull cannot silently serve a newer
# client. On mismatch the daemon refuses loudly and the `run` CLI falls
# back to a cold in-process launch.
PROTO_VERSION = 1


def checkout_token():
    """Hash of every .py file in the package (not just this file): the
    daemon warm-imports runtime/task/cli, so staleness anywhere in the
    framework must flip the token."""
    pkg_dir = os.path.dirname(os.path.abspath(__file__))
    h = hashlib.sha256()
    try:
        for root, dirs, files in sorted(os.walk(pkg_dir)):
            dirs.sort()
            for name in sorted(files):
                if not name.endswith(".py"):
                    continue
                path = os.path.join(root, name)
                h.update(os.path.relpath(path, pkg_dir).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    except OSError:
        return "unknown"
    return h.hexdigest()[:16]


def default_socket_path():
    return knobs.get_str(
        "TPUFLOW_DAEMON_SOCKET",
        fallback=os.path.join(tempfile.gettempdir(),
                              "tpuflow-daemon-%d.sock" % os.getuid()),
    )


def _pidfile(sock_path):
    return sock_path + ".pid"


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------


class SchedulerDaemon(object):
    def __init__(self, sock_path=None):
        self.sock_path = sock_path or default_socket_path()
        self._listener = None
        self._shutdown = threading.Event()
        # hashed at construction: reflects the code this daemon is running,
        # not whatever lands on disk later
        self._token = checkout_token()

    def _warm_imports(self):
        """Pay the heavy imports once, before the first fork. Module
        imports only — no backend/device initialization, so each child's
        env still controls where jax runs at first use."""
        import importlib

        for mod in ("jax", "numpy", "metaflow_tpu", "metaflow_tpu.cli",
                    "metaflow_tpu.runtime", "metaflow_tpu.task"):
            try:
                importlib.import_module(mod)
            except Exception:
                pass  # a missing optional never blocks serving

    def serve(self):
        try:
            os.unlink(self.sock_path)
        except OSError:
            pass
        self._warm_imports()
        self._listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._listener.bind(self.sock_path)
        # the daemon executes client-supplied argv as this user: never let
        # a permissive umask open that to other local users
        os.chmod(self.sock_path, 0o600)
        self._listener.listen(16)
        with open(_pidfile(self.sock_path), "w") as f:
            f.write(str(os.getpid()))
        signal.signal(signal.SIGTERM, lambda *a: self._stop())
        try:
            while not self._shutdown.is_set():
                try:
                    conn, _ = self._listener.accept()
                except OSError:
                    break  # listener closed by _stop
                self._handle(conn)
        finally:
            self._cleanup()

    def _stop(self):
        self._shutdown.set()
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass

    def _cleanup(self):
        for path in (self.sock_path, _pidfile(self.sock_path)):
            try:
                os.unlink(path)
            except OSError:
                pass

    # ------------------------------------------------------------------

    def _handle(self, conn):
        """One launch request. Forks on the accept (main) thread; a reaper
        thread per child waits and reports the exit code."""
        fds = []

        def refuse(err):
            for fd in fds:  # received via SCM_RIGHTS: never leak them
                os.close(fd)
            try:
                conn.sendall(
                    (json.dumps({"error": err}) + "\n").encode())
            except OSError:
                pass
            conn.close()

        try:
            # Linux-only (macOS/BSD spell it LOCAL_PEERCRED): when
            # unavailable the 0600 socket mode is the sole gate, which is
            # still a same-uid guarantee on any sane filesystem
            _, uid, _ = struct.unpack(
                "3i", conn.getsockopt(socket.SOL_SOCKET,
                                      socket.SO_PEERCRED,
                                      struct.calcsize("3i")))
        except (OSError, AttributeError):
            uid = None
        if uid is not None and uid != os.getuid():
            # belt to the 0600 braces: holds even if the socket was
            # created under an older checkout/umask
            refuse("peer uid %r != %d" % (uid, os.getuid()))
            return
        try:
            # a hung client must not wedge the accept loop: bound the
            # header read (forks stay on this thread by design)
            conn.settimeout(10)
            msg, fds, _flags, _addr = socket.recv_fds(conn, 1 << 20, 3)
            # ONE recvmsg returns at most the socket buffer (~208 KiB
            # default): a big client env can straddle reads, so keep
            # recv'ing until the JSON parses or the 1 MiB cap trips
            while True:
                try:
                    req = json.loads(msg.decode("utf-8"))
                    break
                except ValueError:
                    if len(msg) > (1 << 20):
                        raise
                    more = conn.recv(1 << 20)
                    if not more:
                        raise
                    msg += more
            conn.settimeout(None)
        except (OSError, ValueError):
            for fd in fds:  # received via SCM_RIGHTS before the failure
                os.close(fd)
            conn.close()
            return
        if req.get("op") == "ping":
            for fd in fds:
                os.close(fd)
            try:
                # a client that timed out and hung up must not unwind the
                # accept loop (serve() has no per-connection guard)
                conn.sendall((json.dumps(
                    {"ok": True, "proto": PROTO_VERSION,
                     "token": self._token}
                ) + "\n").encode())
            except OSError:
                pass
            conn.close()
            return
        if (req.get("proto") != PROTO_VERSION
                or req.get("token") != self._token):
            refuse(
                "handshake mismatch (client proto=%r token=%r, daemon "
                "proto=%r token=%r): restart the daemon from this checkout"
                % (req.get("proto"), req.get("token"),
                   PROTO_VERSION, self._token))
            return
        if len(fds) != 3:
            refuse("need stdin/stdout/stderr fds")
            return

        pid = os.fork()
        if pid == 0:
            # child: become the flow process
            self._child(req, fds, conn)
            os._exit(70)  # unreachable
        # parent: hand the fds back, report pid, reap in a thread
        for fd in fds:
            os.close(fd)
        try:
            conn.sendall((json.dumps({"pid": pid}) + "\n").encode())
        except OSError:
            pass

        def reap():
            _, status = os.waitpid(pid, 0)
            code = os.waitstatus_to_exitcode(status)
            if code < 0:  # killed by signal N → conventional 128+N
                code = 128 - code
            try:
                conn.sendall((json.dumps({"exit": code}) + "\n").encode())
            except OSError:
                pass
            conn.close()

        threading.Thread(target=reap, daemon=True).start()

    def _child(self, req, fds, conn):
        # no imports here: the fork child may inherit a held import lock
        # from the reaper threads, which nothing will ever release
        code = 1
        try:
            # shed the daemon's signal handlers — the run must die on the
            # SIGTERM/SIGINT the client forwards, not toggle daemon state
            for signum in (signal.SIGTERM, signal.SIGINT):
                signal.signal(signum, signal.SIG_DFL)
            conn.close()
            self._listener.close()
            for std_fd, fd in zip((0, 1, 2), fds):
                os.dup2(fd, std_fd)
                os.close(fd)
            os.chdir(req.get("cwd", "."))
            env = req.get("env")
            if env is not None:
                os.environ.clear()
                os.environ.update(env)
            argv = req["argv"]
            sys.argv = list(argv)
            runpy.run_path(argv[0], run_name="__main__")
            code = 0
        except SystemExit as ex:
            code = ex.code if isinstance(ex.code, int) else (
                0 if ex.code is None else 1)
        except BaseException:
            traceback.print_exc()
            code = 1
        finally:
            try:
                sys.stdout.flush()
                sys.stderr.flush()
            except Exception:
                pass
            os._exit(code)  # never run the daemon's atexit machinery


# ---------------------------------------------------------------------------
# client
# ---------------------------------------------------------------------------


class DaemonUnavailable(Exception):
    pass


def run_via_daemon(argv, sock_path=None, cwd=None, env=None,
                   stdio=(0, 1, 2)):
    """Launch `argv` (a flow command line) in the daemon; returns the exit
    code. Forwards SIGINT/SIGTERM to the child. Raises DaemonUnavailable
    when no daemon is listening."""
    sock_path = sock_path or default_socket_path()
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        sock.connect(sock_path)
    except OSError as ex:
        raise DaemonUnavailable(
            "no scheduler daemon at %s (start one: python -m "
            "metaflow_tpu.daemon start)" % sock_path
        ) from ex
    req = {
        "proto": PROTO_VERSION,
        "token": checkout_token(),
        "argv": list(argv),
        "cwd": cwd or os.getcwd(),
        "env": dict(env if env is not None else os.environ),
    }
    socket.send_fds(sock, [json.dumps(req).encode("utf-8")], list(stdio))

    reader = sock.makefile("r")
    first = json.loads(reader.readline() or "{}")
    if "pid" not in first:
        raise DaemonUnavailable("daemon refused: %r" % first)
    child_pid = first["pid"]

    prev = {}

    def forward(signum, _frame):
        try:
            os.kill(child_pid, signum)
        except OSError:
            pass

    for signum in (signal.SIGINT, signal.SIGTERM):
        try:
            prev[signum] = signal.signal(signum, forward)
        except ValueError:
            pass  # non-main thread
    try:
        final = json.loads(reader.readline() or '{"exit": 1}')
    finally:
        for signum, handler in prev.items():
            signal.signal(signum, handler)
        sock.close()
    return int(final.get("exit", 1))


def ping(sock_path=None, timeout=2.0):
    sock_path = sock_path or default_socket_path()
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.settimeout(timeout)
    try:
        sock.connect(sock_path)
        socket.send_fds(sock, [b'{"op": "ping"}'], [])
        return b"ok" in sock.recv(256)
    except OSError:
        return False
    finally:
        sock.close()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _cmd_start(args):
    detach = "--detach" in args
    daemon = SchedulerDaemon()
    if detach:
        log_path = os.path.join(tempfile.gettempdir(), "tpuflow-daemon.log")
        if os.fork():
            print("daemon starting (socket %s, log %s)"
                  % (daemon.sock_path, log_path))
            return 0
        os.setsid()
        log = open(log_path, "ab", buffering=0)
        os.dup2(log.fileno(), 1)
        os.dup2(log.fileno(), 2)
        devnull = os.open(os.devnull, os.O_RDONLY)
        os.dup2(devnull, 0)
    daemon.serve()
    return 0


def _cmd_stop(_args):
    path = _pidfile(default_socket_path())
    try:
        with open(path) as f:
            pid = int(f.read().strip())
        os.kill(pid, signal.SIGTERM)
        print("daemon stopped (pid %d)" % pid)
        return 0
    except (OSError, ValueError):
        print("no daemon running")
        return 1


def _cmd_status(_args):
    if ping():
        print("daemon: running (socket %s)" % default_socket_path())
        return 0
    print("daemon: not running")
    return 1


def main(argv):
    if not argv:
        print(__doc__)
        return 2
    cmd, rest = argv[0], argv[1:]
    if cmd == "start":
        return _cmd_start(rest)
    if cmd == "stop":
        return _cmd_stop(rest)
    if cmd == "status":
        return _cmd_status(rest)
    if cmd == "run":
        if not rest:
            print("usage: python -m metaflow_tpu.daemon run flow.py ...")
            return 2
        try:
            return run_via_daemon(rest)
        except DaemonUnavailable as ex:
            # no daemon, or a handshake mismatch: cold launch instead of
            # failing the run (the warm path is an optimization, never a
            # requirement)
            print("%s; falling back to a cold launch" % ex,
                  file=sys.stderr)
            import subprocess

            return subprocess.run([sys.executable] + list(rest)).returncode
    print("unknown daemon command %r" % cmd)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
