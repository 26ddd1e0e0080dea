"""The LDBC Social Network Benchmark's social network (arXiv:2001.02299),
as the benchmark builds it: a copy of the program's
``repro.graph.generators.ldbc_snb_like``, kept with the benchmark so that
the deployment's data cannot move when a later change edits the
program's generator.  ``bench/tests/test_ldbc_copy.py`` checks that both
build the same graph, byte for byte, at a small scale.

A configuration names it as ``"graph": {"generator": "ldbc_snb",
"scale_factor": ...}``.
"""

from __future__ import annotations

import numpy as np

from yardstick import twin


def generate(seed: int, **params) -> twin.Graph:
    return ldbc_snb_like(seed=seed, **params)


# The 15 edge labels of the LDBC SNB schema (arXiv:2001.02299 §2), in the
# order the label ids take.
LDBC_LABELS = [
    "knows", "hasCreator", "replyOf", "containerOf", "hasMember",
    "hasModerator", "hasTag", "hasInterest", "likes", "isLocatedIn",
    "isPartOf", "studyAt", "workAt", "hasType", "isSubclassOf",
]

# Entity counts that do not grow with the scale factor: Datagen reads
# places, organisations, tags and tag classes from fixed dictionaries.
LDBC_STATIC = {
    "continents": 6, "countries": 111, "cities": 1343,
    "universities": 6380, "companies": 1575, "tag_classes": 71, "tags": 16080,
}

# Persons at SF0.1, and the growth exponent over scale factors (SF1 about
# 10k persons, SF10 about 70k); per-person activity means at every scale.
LDBC_PERSONS_SF01 = 1528
LDBC_PERSON_GROWTH = 0.83
LDBC_MEANS = {
    "knows_degree": 14.0, "interests": 28.0, "study_share": 0.8, "work": 2.2,
    "albums": 3.0, "groups": 5.0, "group_members": 10.0,
    "wall_posts": 30.0, "album_posts": 10.0, "group_posts": 6.0,
    "post_tags": 1.0, "comments_per_post": 1.2, "likes_per_message": 0.38,
}


def _lomax_counts(rng: np.random.Generator, n: int, mean: float, shape: float, cap: int) -> np.ndarray:
    """Non-negative power-law counts: floor of a Lomax draw with the given
    tail ``shape`` and (before the floor and the cap) the given mean."""
    scale = mean * (shape - 1.0)
    return np.minimum(np.floor(rng.pareto(shape, n) * scale + 0.5), cap).astype(np.int64)


def _pick(rng: np.random.Generator, offsets: np.ndarray, values: np.ndarray,
          owners: np.ndarray, fallback: np.ndarray) -> np.ndarray:
    """For each owner, one uniform draw from its CSR row of ``values``
    (``offsets`` over owners); ``fallback`` where the row is empty."""
    lo, hi = offsets[owners], offsets[owners + 1]
    k = lo + np.floor(rng.random(len(owners)) * (hi - lo)).astype(np.int64)
    out = np.asarray(fallback, np.int64).copy()
    has = hi > lo
    out[has] = values[k[has]]
    return out


def _csr(owner: np.ndarray, value: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray]:
    order = np.argsort(owner, kind="stable")
    offsets = np.zeros(n + 1, np.int64)
    np.add.at(offsets[1:], owner, 1)
    return np.cumsum(offsets), value[order]


def ldbc_snb_like(
    scale_factor: float = 0.1, seed: int = 0
) -> twin.Graph:
    """An LDBC SNB social network, as Datagen's specification describes it
    (arXiv:2001.02299 §2-3), at ``scale_factor``.  Deterministic for a
    given seed.

    Node ids are numbered by entity type, in creation order within a
    type: places (continents, countries, cities), organisations
    (universities, companies), tag classes, tags, persons, forums, posts
    (grouped by forum), comments (grouped by the thread they reply in).
    Edges follow the schema's directions; ``knows`` is undirected and
    emitted both ways.  Degrees and correlations follow the
    specification's description: ``knows`` power-law, built in three
    passes over persons sorted by university, by main interest and at
    random, each joining persons near in that order; messages per person
    power-law; reply trees of varying depth; one wall per person plus
    albums and groups; message tags drawn from their forum's.  Counts the
    specification does not give are the module's ``LDBC_*`` constants."""
    rng = np.random.default_rng(seed)
    st = LDBC_STATIC
    n_persons = max(int(round(LDBC_PERSONS_SF01 * (scale_factor / 0.1) ** LDBC_PERSON_GROWTH)), 8)
    m = LDBC_MEANS
    lid = {name: i for i, name in enumerate(LDBC_LABELS)}
    src_l: list[np.ndarray] = []
    lbl_l: list[np.ndarray] = []
    dst_l: list[np.ndarray] = []

    def add(label: str, s, d) -> None:
        s = np.asarray(s, np.int64)
        src_l.append(s.astype(np.int32))
        lbl_l.append(np.full(len(s), lid[label], np.int32))
        dst_l.append(np.asarray(d, np.int64).astype(np.int32))

    # ---- id ranges, by type ---------------------------------------------
    base = 0

    def block(n: int) -> np.ndarray:
        nonlocal base
        ids = np.arange(base, base + n, dtype=np.int64)
        base += n
        return ids

    continents = block(st["continents"])
    countries = block(st["countries"])
    cities = block(st["cities"])
    universities = block(st["universities"])
    companies = block(st["companies"])
    tag_classes = block(st["tag_classes"])
    tags = block(st["tags"])
    persons = block(n_persons)

    # ---- places: cities in countries, countries in continents -------------
    country_w = 1.0 / np.arange(1, len(countries) + 1) ** 1.0
    country_w /= country_w.sum()
    country_continent = rng.integers(0, len(continents), len(countries))
    add("isPartOf", countries, continents[country_continent])
    # every country has a city; the rest follow the population weights
    city_country = np.concatenate([
        np.arange(len(countries)),
        rng.choice(len(countries), len(cities) - len(countries), p=country_w),
    ])
    city_country = np.sort(city_country)  # a country's cities are consecutive
    add("isPartOf", cities, countries[city_country])

    # ---- organisations ------------------------------------------------------
    uni_city = np.sort(rng.integers(0, len(cities), len(universities)))
    add("isLocatedIn", universities, cities[uni_city])
    comp_country = np.sort(rng.choice(len(countries), len(companies), p=country_w))
    add("isLocatedIn", companies, countries[comp_country])

    # ---- tag classes (a tree under the root class 0) and tags -------------
    k = np.arange(1, len(tag_classes))
    parent = np.floor(k * rng.random(len(k)) ** 2).astype(np.int64)
    add("isSubclassOf", tag_classes[k], tag_classes[parent])
    class_w = 1.0 / np.arange(1, len(tag_classes) + 1) ** 0.8
    class_w /= class_w.sum()
    tag_class = np.sort(rng.choice(len(tag_classes), len(tags), p=class_w))
    add("hasType", tags, tag_classes[tag_class])
    tag_w = 1.0 / np.arange(1, len(tags) + 1) ** 1.1
    tag_w /= tag_w.sum()

    # ---- persons: place, study, work, interests -----------------------------
    p_country = rng.choice(len(countries), n_persons, p=country_w)
    c_off, c_cities = _csr(city_country, np.arange(len(cities)), len(countries))
    p_city = _pick(rng, c_off, c_cities, p_country, np.zeros(n_persons, np.int64))
    add("isLocatedIn", persons, cities[p_city])
    u_off, u_unis = _csr(city_country[uni_city], np.arange(len(universities)), len(countries))
    p_uni = _pick(rng, u_off, u_unis, p_country, rng.integers(0, len(universities), n_persons))
    studies = rng.random(n_persons) < m["study_share"]
    add("studyAt", persons[studies], universities[p_uni[studies]])
    n_work = rng.poisson(m["work"], n_persons)
    w_owner = np.repeat(np.arange(n_persons), n_work)
    k_off, k_comps = _csr(comp_country, np.arange(len(companies)), len(countries))
    home = rng.random(len(w_owner)) < 0.9
    w_comp = np.where(
        home,
        _pick(rng, k_off, k_comps, p_country[w_owner], rng.integers(0, len(companies), len(w_owner))),
        rng.integers(0, len(companies), len(w_owner)),
    )
    add("workAt", persons[w_owner], companies[w_comp])
    # interests: half from the person's country's own block of tags, half
    # from the global popularity; the first is the main interest
    n_int = 1 + rng.poisson(m["interests"] - 1.0, n_persons)
    i_owner = np.repeat(np.arange(n_persons), n_int)
    local = rng.random(len(i_owner)) < 0.5
    span = min(200, len(tags) // 2)
    country_tag0 = (p_country[i_owner] * 131) % (len(tags) - span)
    i_tag = np.where(
        local,
        country_tag0 + np.floor(rng.random(len(i_owner)) ** 2 * span).astype(np.int64),
        rng.choice(len(tags), len(i_owner), p=tag_w),
    )
    add("hasInterest", persons[i_owner], tags[i_tag])
    main_interest = i_tag[np.concatenate([[0], np.cumsum(n_int)[:-1]])]

    # ---- knows: three correlated passes, undirected -------------------------
    degree = np.maximum(_lomax_counts(rng, n_persons, m["knows_degree"], 1.8, n_persons // 2), 1)
    pairs = []
    for key, share in ((p_uni, 0.45), (main_interest, 0.45), (rng.random(n_persons), 0.10)):
        order = np.lexsort((rng.random(n_persons), key))
        pos = np.empty(n_persons, np.int64)
        pos[order] = np.arange(n_persons)
        n_new = rng.binomial(degree, share)
        a = np.repeat(np.arange(n_persons), n_new)
        # a person of degree d draws partners about d/2 places away, so a
        # hub's edges do not collapse onto its nearest neighbours
        step = rng.geometric(np.clip(2.0 / (2.0 + degree[a]), 0.002, 0.5))
        step *= np.where(rng.random(len(a)) < 0.5, 1, -1)
        b = order[(pos[a] + step) % n_persons]
        pairs.append(np.stack([np.minimum(a, b), np.maximum(a, b)], 1))
    pairs = np.unique(np.concatenate(pairs), axis=0)
    pairs = pairs[pairs[:, 0] != pairs[:, 1]]
    add("knows", persons[pairs[:, 0]], persons[pairs[:, 1]])
    add("knows", persons[pairs[:, 1]], persons[pairs[:, 0]])
    f_off, f_vals = _csr(
        np.concatenate([pairs[:, 0], pairs[:, 1]]),
        np.concatenate([pairs[:, 1], pairs[:, 0]]),
        n_persons,
    )

    # ---- forums: per person a wall, albums and groups, in person order -----
    n_alb = rng.poisson(m["albums"], n_persons)
    n_grp = rng.poisson(m["groups"], n_persons)
    per = 1 + n_alb + n_grp
    fo_mod = np.repeat(np.arange(n_persons), per)
    rank = np.arange(len(fo_mod)) - np.repeat(np.cumsum(per) - per, per)
    fo_kind = np.where(rank == 0, 0, np.where(rank <= np.repeat(n_alb, per), 1, 2))
    forums = block(len(fo_mod))
    add("hasModerator", forums, persons[fo_mod])
    # members: a wall's and an album's are the moderator's friends (an
    # album's each with probability 1/2); a group's, friends and strangers
    deg_mod = f_off[fo_mod + 1] - f_off[fo_mod]
    mem_forum, mem_person = [], []
    wall_or_album = fo_kind < 2
    fa = np.nonzero(wall_or_album)[0]
    fa_rep = np.repeat(fa, deg_mod[fa])
    fa_friend = f_vals[np.concatenate([np.arange(f_off[p], f_off[p + 1]) for p in fo_mod[fa]])]
    keep = (fo_kind[fa_rep] == 0) | (rng.random(len(fa_rep)) < 0.5)
    mem_forum.append(fa_rep[keep])
    mem_person.append(fa_friend[keep])
    fg = np.nonzero(~wall_or_album)[0]
    n_mem = _lomax_counts(rng, len(fg), m["group_members"], 2.0, n_persons // 2)
    g_rep = np.repeat(fg, n_mem)
    friendly = rng.random(len(g_rep)) < 0.5
    g_person = np.where(
        friendly,
        _pick(rng, f_off, f_vals, fo_mod[g_rep], fo_mod[g_rep]),
        rng.integers(0, n_persons, len(g_rep)),
    )
    mem_forum.append(g_rep)
    mem_person.append(g_person)
    mem = np.unique(np.stack([np.concatenate(mem_forum), np.concatenate(mem_person)], 1), axis=0)
    add("hasMember", forums[mem[:, 0]], persons[mem[:, 1]])
    m_off, m_vals = _csr(mem[:, 0], mem[:, 1], len(forums))
    # forum tags: from the moderator's interests
    n_ftag = np.where(fo_kind == 0, 3, np.where(fo_kind == 1, 1, 2))
    ft_forum = np.repeat(np.arange(len(forums)), n_ftag)
    in_off, in_tags = _csr(i_owner, i_tag, n_persons)
    ft_tag = _pick(rng, in_off, in_tags, fo_mod[ft_forum], rng.integers(0, len(tags), len(ft_forum)))
    ftags = np.unique(np.stack([ft_forum, ft_tag], 1), axis=0)
    add("hasTag", forums[ftags[:, 0]], tags[ftags[:, 1]])
    t_off, t_vals = _csr(ftags[:, 0], ftags[:, 1], len(forums))

    # ---- posts, grouped by forum -------------------------------------------
    post_mean = np.array([m["wall_posts"], m["album_posts"], m["group_posts"]])[fo_kind]
    n_posts = np.minimum(np.floor(rng.pareto(2.0, len(forums)) * post_mean + 0.5), 2000)
    n_posts = n_posts.astype(np.int64)
    po_forum = np.repeat(np.arange(len(forums)), n_posts)
    posts = block(len(po_forum))
    add("containerOf", forums[po_forum], posts)
    po_creator = np.where(
        fo_kind[po_forum] == 2,
        _pick(rng, m_off, m_vals, po_forum, fo_mod[po_forum]),
        fo_mod[po_forum],
    )
    add("hasCreator", posts, persons[po_creator])
    n_ptag = rng.poisson(m["post_tags"], len(posts))
    pt_post = np.repeat(np.arange(len(posts)), n_ptag)
    pt_tag = _pick(rng, t_off, t_vals, po_forum[pt_post], rng.integers(0, len(tags), len(pt_post)))
    ptags = np.unique(np.stack([pt_post, pt_tag], 1), axis=0)
    add("hasTag", posts[ptags[:, 0]], tags[ptags[:, 1]])

    # ---- comments: reply trees per post, grouped by thread -----------------
    n_com = _lomax_counts(rng, len(posts), m["comments_per_post"], 1.5, 300)
    co_post = np.repeat(np.arange(len(posts)), n_com)
    co_rank = np.arange(len(co_post)) - np.repeat(np.cumsum(n_com) - n_com, n_com)
    comments = block(len(co_post))
    # a reply answers the post, the comment just before it (a chain that
    # deepens the tree) or any earlier comment of its thread
    u = rng.random(len(co_post))
    earlier = np.floor(rng.random(len(co_post)) * co_rank).astype(np.int64)
    parent_rank = np.where(u < 0.6, co_rank - 1, earlier)
    to_post = (co_rank == 0) | (u < 0.3)
    parent = np.where(to_post, posts[co_post], comments[np.arange(len(co_post)) - co_rank + parent_rank])
    add("replyOf", comments, parent)
    co_creator = _pick(rng, f_off, f_vals, po_creator[co_post], po_creator[co_post])
    add("hasCreator", comments, persons[co_creator])
    pp_off, pp_tags = _csr(ptags[:, 0], ptags[:, 1], len(posts))
    n_ctag = rng.poisson(1.2, len(comments))
    ct_com = np.repeat(np.arange(len(comments)), n_ctag)
    ct_tag = _pick(rng, pp_off, pp_tags, co_post[ct_com], rng.choice(len(tags), len(ct_com), p=tag_w))
    ctags = np.unique(np.stack([ct_com, ct_tag], 1), axis=0)
    add("hasTag", comments[ctags[:, 0]], tags[ctags[:, 1]])

    # ---- message places and likes -------------------------------------------
    msg = np.concatenate([posts, comments])
    msg_creator = np.concatenate([po_creator, co_creator])
    abroad = rng.random(len(msg)) < 0.1
    msg_country = np.where(abroad, rng.choice(len(countries), len(msg), p=country_w), p_country[msg_creator])
    add("isLocatedIn", msg, countries[msg_country])
    n_like = _lomax_counts(rng, len(msg), m["likes_per_message"], 1.8, n_persons // 2)
    lk_msg = np.repeat(np.arange(len(msg)), n_like)
    liker = _pick(rng, f_off, f_vals, msg_creator[lk_msg], rng.integers(0, n_persons, len(lk_msg)))
    likes = np.unique(np.stack([liker, lk_msg], 1), axis=0)
    add("likes", persons[likes[:, 0]], msg[likes[:, 1]])

    g = twin.Graph(
        base,
        np.concatenate(src_l),
        np.concatenate(lbl_l),
        np.concatenate(dst_l),
        list(LDBC_LABELS),
    )
    return g.dedup()
