"""Nothing lives here.  The four ragged paged kernels this module held
never served a request on the chip and went with ISSUE 49; the fused
tick's attention is ``ops.attention.paged_decode`` / ``ragged_verify``.

The module stays because ``benchmark/tools/compile_check.py`` imports it
by name and sets ``_interpret`` on it, and a ``simplicity`` PR may not
edit ``benchmark/``.  Delete it with the next ``benchmark`` issue
(ROADMAP C0), together with that tool's three pokes.
"""
