"""Stub HTTP servers for the generator's tests: threads over plain
sockets, one request at a time per connection."""

import os
import signal
import socket
import threading
import time

from lib import harness

OK = (b"HTTP/1.1 200 OK\r\ncontent-type: text/plain\r\ncontent-length: 4\r\n"
      b"connection: keep-alive\r\n\r\npong")
BLOCK = (b"HTTP/1.1 403 Forbidden\r\ncontent-length: 9\r\n"
         b"connection: close\r\n\r\nForbidden")


class StubServer:
    """`behave(request_head: bytes, n: int) -> bytes | None | "reset"`
    decides each answer; n counts requests over all connections."""

    def __init__(self, behave):
        self.behave = behave
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(256)
        self.port = self.sock.getsockname()[1]
        self.count = 0
        self.lock = threading.Lock()
        self.open = True
        self.conns = []
        self.peers = []     # the address each connection came from
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while self.open:
            try:
                conn, peer = self.sock.accept()
            except OSError:
                return
            self.conns.append(conn)
            self.peers.append(peer[0])
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn):
        buf = b""
        try:
            while True:
                while b"\r\n\r\n" not in buf:
                    chunk = conn.recv(65536)
                    if not chunk:
                        return
                    buf += chunk
                head, _, buf = buf.partition(b"\r\n\r\n")
                with self.lock:
                    self.count += 1
                    n = self.count
                answer = self.behave(head, n)
                if answer == "reset":
                    conn.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                    b"\x01\x00\x00\x00\x00\x00\x00\x00")
                    conn.close()
                    return
                if answer is None:
                    continue
                conn.sendall(answer)
                if b"connection: close" in answer:
                    conn.close()
                    return
        except OSError:
            pass

    def close(self):
        """Stop listening and drop every connection (a server killed)."""
        self.open = False
        try:
            self.sock.shutdown(socket.SHUT_RDWR)   # wakes the accept
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass
        for conn in self.conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
                conn.close()
            except OSError:
                pass


def stall_between(t_from: float, t_to: float, t0: list):
    """Answers 200, but sleeps through [t_from, t_to) after t0[0]."""
    def behave(_head, _n):
        now = time.monotonic() - t0[0]
        if t_from <= now < t_to:
            time.sleep(t_to - now)
        return OK
    return behave


def hold_up(monkeypatch, at_s: float, hold_s: float) -> None:
    """Wrap Run.window so that the served Python process (the sidecar
    that drains the ring and drives the device) is stopped with SIGSTOP
    `at_s` seconds after the window opens and continued `hold_s`
    seconds later; the native plane, the upstream and the generator run
    on."""
    window = harness.Run.window

    def stop_and_go(pid: int) -> None:
        try:
            os.kill(pid, signal.SIGSTOP)
            time.sleep(hold_s)
        finally:
            os.kill(pid, signal.SIGCONT)

    def held_up(self):
        timer = threading.Timer(harness.LEAD_IN_S + at_s, stop_and_go,
                                (self.server.proc.pid,))
        timer.daemon = True
        timer.start()
        try:
            return window(self)
        finally:
            timer.cancel()

    monkeypatch.setattr(harness.Run, "window", held_up)
