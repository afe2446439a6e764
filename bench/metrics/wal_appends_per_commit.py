"""WAL group commit: appends per commit group over the window, summed
over shards; how many writes one sync carries."""


def read(ctx):
    appends = ctx.delta("store_wal_appends_total")
    commits = ctx.delta("store_wal_commits_total")
    if appends is None or not commits:
        return None
    return appends / commits
