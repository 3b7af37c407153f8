"""95th percentile, over the requests submitted in the window, of submit ->
admit dispatch as the server's own ``serve_request`` event records it
(``queue_wait_s``): a slot is free when its client asks again, so this is
the wait for the admissions queued before one's own.

``queue_wait_p95_ms.granite``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("queue_wait_p95_ms.granite")
