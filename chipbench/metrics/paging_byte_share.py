"""Share (%) of the window's ingress bytes that came by the paging path
(whole pages) rather than the object path (single records)."""


def read(rec):
    page = rec.stats["page_ins"] * rec.page_bytes
    obj = rec.stats["obj_ins"] * rec.row_bytes
    return 100.0 * page / (page + obj) if page + obj else None
