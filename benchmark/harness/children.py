"""Child processes that drive traffic.  They are spawned, never forked
(the parent holds threads and the chip), import only the benchmark's
own modules and never JAX, and answer the parent over a pipe."""

import multiprocessing
import sys
import traceback


def context():
    return multiprocessing.get_context('spawn')


class ChildFailed(RuntimeError):
    pass


def _entry(target, ctl, spec):
    try:
        target(ctl, spec)
    except BaseException:
        ctl.send(('failed', traceback.format_exc()))
        raise
    finally:
        if 'jax' in sys.modules:
            ctl.send(('failed', 'a traffic process imported jax'))
        ctl.close()


class Child(object):
    """One spawned process running ``target(ctl, spec)``; `ctl` is its
    end of a duplex pipe of ``(tag, payload)`` messages."""

    def __init__(self, target, spec, name):
        ctx = context()
        self._ctl, theirs = ctx.Pipe()
        self.name = name
        self.proc = ctx.Process(target=_entry, args=(target, theirs, spec),
                                name=name)
        self.proc.start()
        theirs.close()

    def tell(self, tag, payload=None):
        self._ctl.send((tag, payload))

    def wait(self, tag, timeout=None):
        """The payload of the child's next message, which must carry `tag`."""
        if not self._ctl.poll(timeout):
            raise ChildFailed('%s: no %r within %s s' % (self.name, tag,
                                                         timeout))
        try:
            got, payload = self._ctl.recv()
        except (EOFError, OSError):
            raise ChildFailed('%s exited without %r (exit code %s)' % (
                self.name, tag, self.proc.exitcode))
        if got == 'failed':
            raise ChildFailed('%s failed:\n%s' % (self.name, payload))
        if got != tag:
            raise ChildFailed('%s sent %r, expected %r' % (self.name, got,
                                                           tag))
        return payload

    def ask(self, tag, payload=None, timeout=None):
        self.tell(tag, payload)
        return self.wait(tag, timeout)

    def stop(self, timeout=30):
        """Asks the child to exit and waits for it; kills it past
        `timeout`."""
        try:
            self._ctl.send(('close', None))
        except (BrokenPipeError, OSError):
            pass
        self.proc.join(timeout)
        if self.proc.is_alive():
            self.proc.kill()
            self.proc.join(10)
        self._ctl.close()


def serve(ctl, handlers):
    """The child's side: answers each ``(tag, payload)`` with
    ``(tag, handlers[tag](payload))`` until the parent says close."""
    while True:
        try:
            tag, payload = ctl.recv()
        except EOFError:
            return
        if tag == 'close':
            return
        ctl.send((tag, handlers[tag](payload)))
